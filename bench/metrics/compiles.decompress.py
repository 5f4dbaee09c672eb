"""Programs compiled or loaded from the compile cache during the decompress phase.

Counted from JAX's ``/jax/core/compile/backend_compile_duration`` events;
every shape is warmed up in set-up, so a sound run reads 0.
"""


def read(ctx):
    return float(ctx.compiles["decompress"])
