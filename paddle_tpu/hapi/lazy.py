"""Lazy (deferred) scalar losses for the hapi training loop.

The fused K-step train program (jit.TrainStep.scan_steps) returns its
per-step losses as ONE stacked device array; forcing each to a Python
float at step time would reinstate the per-step device->host round-trip
the fused loop removes. Instead the loop hands callbacks ``LazyLoss``
objects: float-like views into a shared ``LossWindow`` that fetches the
WHOLE window in a single sync the first time ANY of its losses is read
(ProgBarLogger at ``log_freq``, the epoch-end materialization, a user
callback calling ``float(loss)``).

``LazyLoss`` registers as :class:`numbers.Real` so numeric-gated
consumers (WandbCallback's ``isinstance(v, numbers.Number)``,
format specs like ``f"{loss:.4f}"``) treat it as the float it will
become — coercion is the sync.
"""
from __future__ import annotations

import numbers

import numpy as np

__all__ = ["LazyLoss", "LossWindow"]


class LossWindow:
    """Shared fetch cache for one window of device losses.

    Holds the stacked ``[K]`` device array (or a single step's scalar);
    the first read materializes the whole window in one device->host
    sync (recorded via framework.syncs) and drops the device reference.
    """

    __slots__ = ("_dev", "_np")

    def __init__(self, device_values):
        self._dev = device_values
        self._np = None

    @property
    def fetched(self) -> bool:
        return self._np is not None

    def fetch(self) -> np.ndarray:
        if self._np is None:
            from ..framework import syncs
            from ..obs.trace import span as _span
            syncs.record_sync()
            # the window's ONE blocking device read — the "fetch" leg
            # of the per-window spans (`train.prefetch_wait` is
            # hapi.Model's fused loop's, `train.window` the step's own)
            with _span("train.fetch", cat="train"):
                self._np = np.asarray(self._dev,
                                      dtype=np.float64).reshape(-1)
            self._dev = None
        return self._np

    def __array__(self, dtype=None):
        # numpy-coercible so StepWatchdog's NaN scan reads the window
        # through the SAME cached fetch the loop's LazyLosses share —
        # one counted sync per supervised window, not a second
        # uncounted device->host transfer
        return np.asarray(self.fetch(), dtype=dtype)


class LazyLoss:
    """A float you pay for only when you read it.

    ``float()``, formatting, arithmetic, and comparisons all coerce
    (one sync per *window*, shared across the window's K losses).
    """

    __slots__ = ("_window", "_index")

    def __init__(self, window: LossWindow, index: int = 0):
        self._window = window
        self._index = index

    # -- coercion (the sync) --------------------------------------------
    def __float__(self) -> float:
        return float(self._window.fetch()[self._index])

    def __int__(self) -> int:
        return int(float(self))

    def __bool__(self) -> bool:
        return bool(float(self))

    def __array__(self, dtype=None):
        return np.asarray(float(self), dtype=dtype)

    # -- presentation ---------------------------------------------------
    def __format__(self, spec: str) -> str:
        return format(float(self), spec)

    def __str__(self) -> str:
        return str(float(self))

    def __repr__(self) -> str:
        if self._window.fetched:
            return f"LazyLoss({float(self)})"
        return "LazyLoss(<on device>)"

    # -- arithmetic / comparisons (all coerce) --------------------------
    def __add__(self, other):
        return float(self) + other

    __radd__ = __add__

    def __sub__(self, other):
        return float(self) - other

    def __rsub__(self, other):
        return other - float(self)

    def __mul__(self, other):
        return float(self) * other

    __rmul__ = __mul__

    def __truediv__(self, other):
        return float(self) / other

    def __rtruediv__(self, other):
        return other / float(self)

    def __neg__(self):
        return -float(self)

    def __abs__(self):
        return abs(float(self))

    def __lt__(self, other):
        return float(self) < other

    def __le__(self, other):
        return float(self) <= other

    def __gt__(self, other):
        return float(self) > other

    def __ge__(self, other):
        return float(self) >= other

    def __eq__(self, other):
        try:
            return float(self) == float(other)
        except (TypeError, ValueError):
            return NotImplemented

    def __ne__(self, other):
        eq = self.__eq__(other)
        return eq if eq is NotImplemented else not eq

    def __hash__(self):
        return hash(float(self))

    def __round__(self, ndigits=None):
        return round(float(self), ndigits)


# numeric-gated consumers (wandb's isinstance(v, numbers.Number)) must
# see LazyLoss as the real number it defers
numbers.Real.register(LazyLoss)
