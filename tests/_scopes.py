"""Compile ``engine_jax.sweep``'s jit program in place of running it, and
read the round-phase name scopes out of the compiled module's op_name
metadata.  Test modules do ``from _scopes import ...``."""

import re
from unittest import mock

from repro.sim import engine_jax


class _Compiled(Exception):
    """Carries the text of a program compiled in place of running it."""


def compiled_sweep_text(*args, **kw) -> str:
    """The optimized HLO text of the program ``engine_jax.sweep(*args,
    **kw)`` would run, with its metadata."""
    fn = engine_jax._run_grid

    def compile_call(*a, **static):
        raise _Compiled(fn.lower(*a, **static).compile().as_text())

    with mock.patch.object(engine_jax, "_run_grid", compile_call):
        try:
            engine_jax.sweep(*args, **kw)
        except _Compiled as c:
            return c.args[0]
    raise AssertionError("sweep() returned without calling _run_grid")


def round_scopes(text: str) -> set[str]:
    """Every ``round.*`` component of the op_name paths in ``text``."""
    return {part for name in re.findall(r'op_name="([^"]*)"', text)
            for part in name.split("/") if part.startswith("round.")}
