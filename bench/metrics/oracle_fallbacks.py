"""oracle_fallbacks: windowed matmuls that resolved to the Pallas arm but
ran the jnp oracle (``kernels.dispatch.ORACLE_FALLBACKS``, summed) when the
round was traced."""


def read(ctx):
    return float(ctx.fallbacks)
