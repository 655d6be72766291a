"""Plain float32 reference of the benchmark's models and optimizers.

Written from the published descriptions (DeepFM, arXiv:1703.04247; a
LLaMA-style dense decoder as Yi-6B, arXiv:2403.04652; D-Adam and CD-Adam,
arXiv:2008.10422) in straightforward ``jax.numpy``. It imports nothing of
the program under test and takes nothing the program has made: weights and
batches come from the seed through :mod:`reference.weights` and the
benchmark's traffic generator.
"""
