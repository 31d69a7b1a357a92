"""Known-bad Pallas kernel for the kernel checker: one ``pallas_call``
that (a) walks its input index map off the end of the array, (b) lets
two *parallel* grid points write the same output block, (c) asks for
more VMEM scratch than the per-step budget, and (d) reads a block whose
lane dim breaks the TPU's (8, 128) tiling.  ``tests/test_audit.py``
captures it under ``PallasCapture`` and asserts ``check_record`` reports
all four."""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS, D = 1024, 256
BLOCK = 256


def _kernel(x_ref, y_ref, o_ref, scratch):
    o_ref[...] = x_ref[...]


def run():
    x = jnp.zeros((ROWS, D), jnp.float32)
    return pl.pallas_call(
        _kernel,
        grid=(ROWS // BLOCK, 2),
        in_specs=[
            # off-by-one: walks one block past the end of x
            pl.BlockSpec((BLOCK, D), lambda i, j: (i + 1, 0)),
            # 64 lanes of a 256-wide array: not a multiple of 128
            pl.BlockSpec((BLOCK, 64), lambda i, j: (i, 0)),
        ],
        # every j writes the same block i — but j is marked "parallel"
        out_specs=pl.BlockSpec((BLOCK, D), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ROWS, D), jnp.float32),
        # 64 MiB scratch: 4x the 16 MiB default budget
        scratch_shapes=[pltpu.VMEM((4096, 4096), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=True,
    )(x, x)
