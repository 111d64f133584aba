"""Pallas TPU kernels for the perf-critical compute layers.

  flash_attention      — blocked attention (LM stack hot spot)
  neighbor_interaction — cell-list pairwise force pass (ABM hot spot)
  delta_codec          — delta encode/decode (paper §2.3)

Each kernel has a pure-jnp oracle in ref.py and a jit'd wrapper in ops.py.
The Pallas interpreter runs kernels on the CPU platform and nowhere else
(``ops.use_interpret``); ``ops.INTERPRET = False`` forces compiled Mosaic
lowering on the CPU, which is how tests/test_tpu_compile.py compiles them
for a described TPU.
"""
