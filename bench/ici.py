"""Bytes the mesh emission's all-gather must move, and the chip-to-chip
interconnect peak they are timed against (``gather_roofline``).

The count is the least any implementation must receive for one close: the
closed interval's cells of every other shard, nothing of the rest of the
ring. So a program that gathers less can raise the share, never read it
over 100%.
"""
from __future__ import annotations

#: Published chip-to-chip interconnect bandwidth per chip, in bytes/s, by
#: ``device_kind``.
ICI_SOURCE = ("Google Cloud documentation, TPU v5e "
              "(https://cloud.google.com/tpu/docs/v5e): 1,600 Gbps of "
              "interchip interconnect per chip")
ICI_BYTES_PER_S = {"TPU v5 lite": 1600e9 / 8}

#: Words a cell carries besides its samples: its arrival count and the
#: number of samples taken (i32 each).
CELL_HEADER_BYTES = 4 + 4


def closed_gather_bytes(shards: int, strata: int, slots: int) -> int:
    """Least bytes one chip receives to merge a closed interval: the
    ``strata`` cells of each of the ``shards - 1`` other shards, each
    ``slots`` f32 samples and its header."""
    return (int(shards) - 1) * int(strata) * (4 * int(slots)
                                              + CELL_HEADER_BYTES)


def ici_bytes_per_s(device_kind: str) -> float:
    """The interconnect peak of ``device_kind``; a device missing from
    the table is an error, not a default."""
    try:
        return ICI_BYTES_PER_S[device_kind]
    except KeyError:
        raise KeyError(f"no interconnect peak for device kind "
                       f"{device_kind!r} in bench/ici.py") from None
