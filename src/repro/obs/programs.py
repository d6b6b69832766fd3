"""The compiled executors of the newest runtime, kept past its close.

A profiler trace names a device operation by its HLO instruction, and the
compiler leaves some of them with no ``op_name`` (on the TPU, the fusion
and the sort that carry a scatter); the executor's compiled HLO text names
them by what is fused into them.  A runtime notes each executor at its
first call: the jitted function and the abstract arguments, no device
buffer.  ``hlo_texts`` lowers them again, which compiles nothing while JAX
still holds the executable.  The process keeps the notes of the runtime
that noted an executor last (``latest_hlo_texts``), so a trace can be read
by the program's names after the runtime that made it is closed.
"""
from __future__ import annotations

from typing import Optional

import jax

__all__ = ["ExecutorNotes", "latest_hlo_texts"]

_latest: Optional["ExecutorNotes"] = None


class ExecutorNotes:
    """One runtime's executors, each as noted at its first call."""

    def __init__(self):
        self._runs: dict = {}

    def note(self, key, fn, args) -> None:
        """Note the executor ``key`` (sortable) unless it was noted: the
        jitted ``fn`` and the abstract shape, dtype and sharding of
        ``args``."""
        global _latest
        if key in self._runs:
            return
        self._runs[key] = (fn, jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding), args))
        _latest = self

    def hlo_texts(self) -> list:
        """The compiled HLO text of every noted executor, in key order."""
        return [fn.lower(*args).compile().as_text()
                for _, (fn, args) in sorted(self._runs.items())]


def latest_hlo_texts() -> list:
    """``hlo_texts`` of the runtime that noted an executor last, or none."""
    return _latest.hlo_texts() if _latest is not None else []
