# Pallas TPU kernels for the framework's compute hot-spots.  Each package:
#   kernel.py  pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
#   ops.py     jit'd public wrapper (layout/padding handling)
#   ref.py     pure-jnp oracle defining the semantics (tests assert_allclose)
# The CPU test suite runs the kernels with interpret=True against ref.py.
# These are the LM substrate's kernels; Enel's decision path runs no Pallas
# kernel, and none of these is compiled for or run on the chip.
