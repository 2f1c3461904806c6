# Pallas TPU kernels for the framework's compute hot-spots.  Each package:
#   kernel.py  pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
#   ops.py     jit'd public wrapper (layout/padding handling)
#   ref.py     pure-jnp oracle defining the semantics (tests assert_allclose)
# The CPU test suite runs the kernels with interpret=True against ref.py.
# graph_prop (forward and custom-VJP backward) is also compiled for a
# described v5e by tests/test_tpu_compile.py and run compiled on the chip
# by chip_smoke.py; the LM kernels are validated in interpret mode only.
