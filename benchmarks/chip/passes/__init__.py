"""Pass kinds, one module each, found by ``<family>_<phase>`` from a cell's
configuration and traffic.  Each module's ``build`` returns a ``Pass``."""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable


@dataclasses.dataclass
class Pass:
    """One cell's compiled layers, ready to drive.

    ``body(params, state, x) -> (state, taps)`` is the pass that the window
    times; the harness jits it once and donates ``state``.  ``inputs`` is
    the pool of inputs, pass i taking ``inputs[i % len(inputs)]``.
    ``calls(i)`` is the work of pass i by kernel call.  ``reference(passes,
    low)`` recomputes the taps of those passes (as ``"<tap>@<i>"``) and,
    where ``inspect`` is given, the taps that ``inspect(state, i)`` reads
    from the state after the last checked pass i, from the seed alone.
    ``check_first`` passes are checked from the start (a decode pass, whose
    state carries on); with 0 the window's last pass is checked.
    ``xla_gemms`` are the (m, n, k, dtype, batch) of the pass's GEMMs, for
    XLA's own lowering of them.
    """
    params: Any
    state: Any
    inputs: list
    body: Callable
    calls: Callable[[int], list]
    reference: Callable[[list, Any], dict]
    xla_gemms: list
    check_first: int = 0
    inspect: Callable | None = None


def bf16_gemms(shapes: list) -> list:
    """(m, n, k) GEMMs as ``xla_gemms`` holds them: bf16, one each."""
    return [(m, n, k, "bf16", 1) for m, n, k in shapes]


def load(kind: str):
    return importlib.import_module(f"passes.{kind}")
