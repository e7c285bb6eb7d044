"""analysis/hlo_check.py — the reusable HLO invariant predicates.

The heavyweight consumers (bench ``fused_carries_window`` gate, the mesh
all-gather witness in tests/test_mesh.py) exercise the real invariants;
this file pins the module's own contract on small functions.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np

from repro.analysis import hlo_check

REPO = os.path.join(os.path.dirname(__file__), "..")


def test_compiled_text_contains_computation():
    def f(a, b):
        return a @ b

    x = jnp.zeros((8, 16), jnp.float32)
    y = jnp.zeros((16, 4), jnp.float32)
    hlo = hlo_check.compiled_text(f, x, y)
    # the output buffer shape must appear in the optimized HLO
    assert hlo_check.count(hlo, hlo_check.stacked_shape("f32", 8, 4)) > 0


def test_compiled_text_static_argnums():
    def f(x, n):
        return jnp.tile(x, n)

    hlo = hlo_check.compiled_text(f, jnp.zeros((4,), jnp.float32), 3,
                                  static_argnums=1)
    assert hlo_check.count(hlo, hlo_check.stacked_shape("f32", 12)) > 0


def test_absence_witness():
    def f(x):
        return x + 1.0

    hlo = hlo_check.compiled_text(f, jnp.zeros((4, 4), jnp.float32))
    # a shape this tiny program never allocates
    assert hlo_check.absent(hlo, hlo_check.stacked_shape("f32", 999, 999))
    assert not hlo_check.absent(hlo, hlo_check.stacked_shape("f32", 4, 4))


def test_count_accepts_str_or_list():
    assert hlo_check.count("aa bb aa", "aa") == 2
    assert hlo_check.count("aa bb aa", ["aa", "bb"]) == 3


def test_has_collective_both_spellings():
    assert hlo_check.has_collective("x = all-gather(y)", "all_gather")
    assert hlo_check.has_collective("x = all_gather(y)", "all-gather")
    assert not hlo_check.has_collective("x = add(y)", "all-gather")


def test_stacked_shape_formats_like_xla():
    assert hlo_check.stacked_shape("f32", 4, 2, 128, 256) == \
        "f32[4,2,128,256]"
    assert hlo_check.stacked_shape("bf16", np.int64(8)) == "bf16[8]"


def test_module_import_is_jax_free():
    # lazy-jax-import contract: importing the module must not import jax
    code = ("import sys; import repro.analysis.hlo_check; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env=dict(os.environ, PYTHONPATH="src"))
    assert proc.returncode == 0
