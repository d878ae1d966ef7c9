"""Sharded serving: batched prefill + single-token decode.

This is the level above ``models.attention``'s documented contract: batched
decode produces one token for every sequence per call with a shared cache
length; *continuous batching* — admitting and retiring sequences in fixed
cache slots so the decode step never recompiles — lives here as
:class:`SlotAllocator`.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional

from repro.models import decode_step, prefill


# ----------------------------------------------------------------------------
# Step builders (pure functions; jit at the call site)
# ----------------------------------------------------------------------------

def make_prefill_step(cfg, max_len: int) -> Callable:
    """(params, batch) -> (last_logits, cache); batch keys mirror training
    minus labels (tokens + optional patches/frames)."""

    def prefill_step(params, batch):
        return prefill(
            cfg,
            params,
            batch["tokens"],
            max_len,
            patches=batch.get("patches"),
            frames=batch.get("frames"),
        )

    return prefill_step


def make_decode_step(cfg) -> Callable:
    """(params, cache, token) -> (logits, cache): one token per sequence."""

    def serve_step(params, cache, token):
        return decode_step(cfg, params, cache, token)

    return serve_step


# ----------------------------------------------------------------------------
# Continuous batching (host-side slot bookkeeping; shapes stay static)
# ----------------------------------------------------------------------------

@dataclass
class SlotAllocator:
    """Fixed-size decode slots for continuous batching.

    The jitted decode step has a static batch dimension; sequences are
    admitted into free slots and retired on EOS/length, so arrivals never
    trigger recompilation.  Purely host-side: the device-side cache is the
    caller's pytree, slot occupancy only gates which rows are live.
    """

    n_slots: int
    active: List[Optional[Any]] = field(default_factory=list)
    admitted: int = 0
    retired: int = 0

    def __post_init__(self):
        if not self.active:
            self.active = [None] * self.n_slots

    @property
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.active) if s is None]

    @property
    def live_mask(self) -> List[bool]:
        return [s is not None for s in self.active]

    def admit(self, request: Any) -> int:
        """Place a request in a free slot; raises when saturated."""
        free = self.free_slots
        if not free:
            raise RuntimeError("no free decode slots")
        slot = free[0]
        self.active[slot] = request
        self.admitted += 1
        return slot

    def retire(self, slot: int) -> Any:
        request = self.active[slot]
        if request is None:
            raise KeyError(f"slot {slot} is not live")
        self.active[slot] = None
        self.retired += 1
        return request

    def utilization(self) -> float:
        return sum(self.live_mask) / max(self.n_slots, 1)
