"""round_recompiles: executables compiled or loaded from the persistent
cache inside the traced window (JAX's backend-compile event, counted by
the harness); set-up compiles every shape the window uses, so a sound run
reads 0.  Layer: ``api.Trainer``'s jitted round."""


def read(ctx):
    return float(ctx.compiles)
