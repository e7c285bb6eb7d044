"""Reusable HLO invariant checks: compile a callable, assert op-count /
absence predicates on the optimized HLO text.

The repo's structural guarantees — the fused client phase materializes
ZERO stacked per-client ``W_sub`` copies, gather-mode mesh rounds lower
a real ``all-gather`` — are witnessed by inspecting compiled HLO, not by
timing.  Those checks used to live as private string-counting helpers in
``benchmarks/run.py`` and ``tests/test_mesh.py``; this module is the one
implementation both consume (and the place to add new witnesses).

Typical use::

    from repro.analysis import hlo_check

    hlo = hlo_check.compiled_text(fn, params, batch, key)
    assert hlo_check.absent(hlo, hlo_check.stacked_shape("f32", C, L, D, w))
    assert hlo_check.has_collective(hlo, "all-gather")

Keep module import jax-free (``lazy-jax-import`` lint rule): jax is
deferred into :func:`compiled_text` so config/reporting code can import
this module without paying for a jax import.
"""
from __future__ import annotations

import re
from typing import Callable, List, Sequence, Set, Union

Patterns = Union[str, Sequence[str]]

_OP_NAME = re.compile(r'op_name="([^"]*)"')
#: Separators of a name-stack path: ``/`` between scopes, and the
#: ``jvp(...)`` / ``transpose(...)`` wrappers ``grad`` puts around them.
_PATH_SEP = re.compile(r"[/()]")


def compiled_text(fn: Callable, *args, static_argnums=None, **kwargs) -> str:
    """Optimized HLO text of ``fn`` compiled on ``args``/``kwargs``.

    ``fn`` is wrapped in ``jax.jit`` (pass ``static_argnums`` through when
    some positions must stay Python values); the args are used for shape/
    dtype inference only — nothing is executed beyond compilation.
    """
    import jax  # deferred: see module docstring

    jitted = (jax.jit(fn) if static_argnums is None
              else jax.jit(fn, static_argnums=static_argnums))
    return jitted.lower(*args, **kwargs).compile().as_text()


def _as_list(patterns: Patterns) -> Sequence[str]:
    return [patterns] if isinstance(patterns, str) else list(patterns)


def count(hlo: str, patterns: Patterns) -> int:
    """Total substring occurrences of the pattern(s) in the HLO text."""
    return sum(hlo.count(p) for p in _as_list(patterns))


def absent(hlo: str, patterns: Patterns) -> bool:
    """True when none of the pattern(s) occur — e.g. a buffer shape that
    must never be allocated."""
    return count(hlo, patterns) == 0


def has_collective(hlo: str, op: str) -> bool:
    """True when the collective ``op`` appears, accepting both HLO
    spellings (``all-gather`` / ``all_gather``)."""
    stem = op.replace("_", "-")
    return stem in hlo or stem.replace("-", "_") in hlo


def stacked_shape(dtype: str, *dims: int) -> str:
    """HLO shape string ``f32[4,2,128,256]`` for an allocation witness —
    the spelling XLA uses in optimized-HLO buffer types."""
    return f"{dtype}[{','.join(str(int(d)) for d in dims)}]"


def op_names(hlo: str) -> List[str]:
    """The ``op_name`` metadata (the ``jax.named_scope`` path) of every
    instruction of ``hlo`` that carries the whole path from its ``jit(``,
    e.g. ``jit(step)/transpose(jvp(fed.client_phase))/dot_general``.
    (Instructions of reducer bodies carry only the tail of a path.)"""
    return [n for n in _OP_NAME.findall(hlo) if n.startswith("jit(")]


def outermost_scope(op_name: str, prefix: str) -> str:
    """The outermost path component of ``op_name`` that starts with
    ``prefix`` (``jvp(`` / ``transpose(`` wrappers stripped), or ``""``."""
    for part in _PATH_SEP.split(op_name):
        if part.startswith(prefix):
            return part
    return ""


def scopes(hlo: str, prefix: str = "fed.") -> Set[str]:
    """The outermost ``prefix`` scopes that own an instruction in ``hlo``."""
    return {outermost_scope(n, prefix) for n in op_names(hlo)} - {""}
