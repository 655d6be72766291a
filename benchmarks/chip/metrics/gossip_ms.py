"""Layer: gossip round. Self time per step of every op of the compiled
step under the program's ``gossip`` scope, kernels and collectives
included: on the fused path the gossip-Adam kernel of a communication
step, so it overlaps ``opt_kernel_ms`` by design."""
from chipbench.marks import scoped_ms


def read(device, ctx):
    return scoped_ms(device, ctx, "gossip",
                     lambda op, names, bwd: "gossip" in names)
