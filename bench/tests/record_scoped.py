"""Records ``data/scoped.xplane.pb`` on a TPU.

    python -m bench.tests.record_scoped bench/tests/data/scoped.xplane.pb

The round is the program's own, small: a gated MLP of width 128 whose
``d_ff`` of 256 is windowed, 2 clients on the two staggered half windows
(the batched-offset Pallas kernels, the per-client scatter-add), K=2 local
steps, driven by ``api.Trainer`` for six traced rounds with the benchmark's
``bench.window`` / ``bench.wait`` spans around its ``repro.*`` spans.  The
batches are made before the window, so the device idles only while the
host is in the program's spans or waits on a loss.

The file keeps what ``bench.trace`` and ``bench.scopes`` read: the device
planes' ``XLA Ops`` and ``Async XLA Ops`` lines, each operation's name and
``tf_op`` scope, and the host's ``bench.*`` and ``repro.*`` spans.
"""
from __future__ import annotations

import os
import shutil
import sys
import tempfile

from bench import scopes as sc
from bench import trace as tr

ROUNDS = 6
D, F, B = 128, 256, 8          # model width, windowed d_ff, rows a client


def _vint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _emit(field: int, value) -> bytes:
    """One varint or length-delimited field."""
    if isinstance(value, int):
        return _vint(field << 3) + _vint(value)
    value = bytes(value)
    return _vint(field << 3 | 2) + _vint(len(value)) + value


def _first(message, field, default=None):
    return next((v for f, v in sc._fields(message) if f == field), default)


def _prune_plane(plane) -> bytes:
    """One XPlane cut to the lines, events and metadata the reductions
    read (empty when none of it is on this plane)."""
    fields = list(sc._fields(plane))
    device = tr.DEVICE_PLANE.match(bytes(_first(plane, 2, b"")).decode())
    metas = dict(sc._map_entries(
        v for f, v in fields if f == sc._XPLANE_EVENT_METADATA))
    stat_names = {k: bytes(_first(v, 2, b"")).decode() for k, v in
                  sc._map_entries(v for f, v in fields
                                  if f == sc._XPLANE_STAT_METADATA)}
    tf_op = [k for k, n in stat_names.items() if n == sc._TF_OP]

    def keep(line_name, event):
        if device:
            return line_name in (tr.OPS_LINE, tr.ASYNC_LINE)
        name = bytes(_first(metas.get(_first(event, 1, 0), b""),
                            sc._XEVENTMETADATA_NAME, b"")).decode()
        return name.startswith((tr.SPAN_PREFIX, sc.PROGRAM_SPAN_PREFIX))

    out, used = bytearray(), set()
    for f, v in fields:
        if f in (1, 2, sc._XPLANE_STAT_METADATA):
            out += _emit(f, v)
        elif f == 3:
            lname = bytes(_first(v, 2, b"")).decode()
            kept = [e for g, e in sc._fields(v) if g == 4 and keep(lname, e)]
            if kept:
                out += _emit(3, b"".join(_emit(g, x) for g, x in
                                         sc._fields(v) if g != 4)
                             + b"".join(_emit(4, e) for e in kept))
                used.update(_first(e, 1, 0) for e in kept)
    if not used:
        return b""
    for k in sorted(used):
        meta = b"".join(
            _emit(g, x) for g, x in sc._fields(metas[k])
            if g in (1, sc._XEVENTMETADATA_NAME)
            or (g == sc._XEVENTMETADATA_STATS and tf_op
                and _first(x, 1) == tf_op[0]))
        out += _emit(sc._XPLANE_EVENT_METADATA,
                     _emit(sc._MAP_KEY, k) + _emit(sc._MAP_VALUE, meta))
    return bytes(out)


def prune(src: str, dst: str) -> None:
    """Write the XSpace ``src`` to ``dst`` cut to what the reductions
    read."""
    with open(src, "rb") as fh:
        buf = memoryview(fh.read())
    out = bytearray()
    for f, plane in sc._fields(buf):
        if f == sc._XSPACE_PLANES:
            kept = _prune_plane(plane)
            if kept:
                out += _emit(f, kept)
    with open(dst, "wb") as fh:
        fh.write(bytes(out))


def small_round():
    """``(fed, params, batches)`` of the small windowed round."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.configs.base import SubmodelConfig
    from repro.models.layers import mlp_apply, mlp_apply_windowed

    def loss(w, batch, window=None):
        spec = window.get("d_ff", F) if window is not None else None
        h = (mlp_apply(w, batch["x"]) if spec is None else
             mlp_apply_windowed(w, batch["x"], spec,
                                backend=window.backend))
        r = h - batch["y"]
        return 0.5 * jnp.mean(r * r), {}

    shapes = {"w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)}
    axes = {"w_gate": ("d_model", "d_ff"), "w_up": ("d_model", "d_ff"),
            "w_down": ("d_ff", "d_model")}
    abstract = {k: jax.ShapeDtypeStruct(s, jnp.float32)
                for k, s in shapes.items()}
    scfg = SubmodelConfig(scheme="rolling", capacity=0.5, stagger=True,
                          local_steps=2, clients_per_round=2, client_lr=0.1)
    fed = api.fed_round((loss, abstract, axes), scfg, fused_forward="on")
    rng = np.random.default_rng(0)
    params = {k: jnp.asarray(rng.standard_normal(s).astype(np.float32)
                             / np.sqrt(s[0])) for k, s in shapes.items()}
    batches = [{"x": rng.standard_normal((2, 2, B, D)).astype(np.float32),
                "y": rng.standard_normal((2, 2, B, D)).astype(np.float32)}
               for _ in range(ROUNDS + 1)]
    return fed, params, batches


def main(argv=None) -> int:
    import jax

    from repro import api

    out = (argv or sys.argv[1:])[0]
    if jax.devices()[0].platform != "tpu":
        print("the fixture is recorded on a TPU", file=sys.stderr)
        return 2
    fed, params, batches = small_round()
    trainer = api.Trainer(fed, params, rng=0)
    float(trainer.step(batches[0])["loss"])   # compiles the round
    span = jax.profiler.TraceAnnotation
    logdir = tempfile.mkdtemp(prefix="scoped_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(logdir, profiler_options=opts):
        with span("bench.window"):
            for batch in batches[1:]:
                rec = trainer.step(batch)
                with span("bench.wait"):
                    float(rec["loss"])
    raw = tr.find_xplane(logdir)
    prune(raw, out)
    print(f"{out}: {os.path.getsize(out)} bytes ({os.path.getsize(raw)} "
          f"recorded), {ROUNDS} rounds, {trainer.compiles} compile")
    shutil.rmtree(logdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
