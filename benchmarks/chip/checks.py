"""What a run is judged by, shared by the drivers and ``control.py``.

A check is a number compared with its limit, ``{name: (value, limit)}``.
A run is correct when every value lies at or under its limit; a missing
limit, or a value that is not a number, fails. The control of a
configuration goes through the same checks with its own number in the
program's place.

The memory a cell reports is what the chips hold while it serves:
``bytes_in_use`` sampled at every step or call boundary of the window,
on the fullest chip. The process's own peak also counts the set-up's
dense weights and packing buffers, which serving no longer holds.
"""
from __future__ import annotations


def correct(checks: dict) -> bool:
    return all(lim is not None and v <= lim for v, lim in checks.values())


def bytes_in_use() -> int:
    """Bytes held now on the fullest local chip."""
    import jax
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in jax.local_devices())


def process_peak_bytes() -> int:
    """The highest ``bytes_in_use`` any local chip has seen in this
    process, set-up included."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())
