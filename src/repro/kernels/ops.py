"""Jit'd public wrappers for the Pallas kernels.

On CPU (this container) the kernels execute with ``interpret=True`` —
the kernel body runs step-by-step in Python/XLA, which is how the tests
validate them against the ref.py oracles. On a real TPU the same calls
compile to Mosaic. ``interpret`` is resolved once per process from the
backend unless overridden.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import fused_adam as _adam
from repro.kernels import gossip as _gossip
from repro.kernels import rwkv_scan as _wkv
from repro.kernels import sign_compress as _sc
from repro.tracing import scoped


def _interpret(override: Optional[bool]) -> bool:
    if override is not None:
        return override
    return jax.default_backend() != "tpu"


def fused_adam(p, g, m, v, *, eta, beta1=0.9, beta2=0.999, tau=1e-6,
               weight_decay=0.0, interpret: Optional[bool] = None):
    return _adam.fused_adam(p, g, m, v, eta=eta, beta1=beta1, beta2=beta2,
                            tau=tau, weight_decay=weight_decay,
                            interpret=_interpret(interpret))


def sign_compress(x, hat, *, interpret: Optional[bool] = None):
    return _sc.sign_compress(x, hat, interpret=_interpret(interpret))


@scoped("compress")
def sign_compress_stacked(x, hat, *, n_true: Optional[int] = None,
                          reduce_axis: Optional[str] = None,
                          interpret: Optional[bool] = None):
    return _sc.sign_compress_stacked(x, hat, n_true=n_true,
                                     reduce_axis=reduce_axis,
                                     interpret=_interpret(interpret))


def gossip_mix(x, offsets, offset_weights, self_weight, *,
               block_rows: Optional[int] = None,
               interpret: Optional[bool] = None):
    kw = {} if block_rows is None else {"block_rows": block_rows}
    return _gossip.gossip_mix(x, offsets, offset_weights, self_weight,
                              interpret=_interpret(interpret), **kw)


def gossip_adam_mix(p, g, m, v, offsets, offset_weights, self_weight, *,
                    eta, beta1=0.9, beta2=0.999, tau=1e-6,
                    weight_decay=0.0, block_rows: Optional[int] = None,
                    interpret: Optional[bool] = None):
    kw = {} if block_rows is None else {"block_rows": block_rows}
    return _gossip.gossip_adam_mix(p, g, m, v, offsets, offset_weights,
                                   self_weight, eta=eta, beta1=beta1,
                                   beta2=beta2, tau=tau,
                                   weight_decay=weight_decay,
                                   interpret=_interpret(interpret), **kw)


def payload_mix(x, payloads, offset_weights, self_weight, *,
                block_rows: Optional[int] = None,
                interpret: Optional[bool] = None):
    kw = {} if block_rows is None else {"block_rows": block_rows}
    return _gossip.payload_mix(x, payloads, offset_weights, self_weight,
                               interpret=_interpret(interpret), **kw)


def consensus_mix(x, hat_self, hat_nbrs, offset_weights, gamma, *,
                  block_rows: Optional[int] = None,
                  interpret: Optional[bool] = None):
    kw = {} if block_rows is None else {"block_rows": block_rows}
    return _gossip.consensus_mix(x, hat_self, hat_nbrs, offset_weights,
                                 gamma, interpret=_interpret(interpret), **kw)


def flash_attention(q, k, v, *, causal=True, window=0, block_q=512,
                    block_kv=512, interpret: Optional[bool] = None):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_kv=block_kv,
                               interpret=_interpret(interpret))


def rwkv_scan(r, k, v, w, u, state, *, chunk=128,
              interpret: Optional[bool] = None):
    return _wkv.rwkv_scan(r, k, v, w, u, state, chunk=chunk,
                          interpret=_interpret(interpret))
