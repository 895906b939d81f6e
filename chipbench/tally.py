"""The program's counter of the kernel path's work in the last step.

``Trainer.fit`` records the last step's tally on the process's metrics bus
(``repro.obs.bus``), one row of the ``tally`` stream, tag ``train``, per
call, as a device array the reader brings to the host. The row holds the
live 128 x 128 tiles of the quantized cotangents, the tiles of their padded
grids, the zero levels and the elements of their live regions, summed over
every kernel-variant projection. A program without the stream gives None.
"""
from __future__ import annotations

COLUMNS = ("tiles_live", "tiles", "zeros", "elements")


def last():
    """The last recorded tally as a dict of ints, or None."""
    from repro.obs.bus import get_bus

    bus = get_bus()
    try:
        if bus.registry.get("tally").columns != COLUMNS:
            return None
    except KeyError:
        return None
    rows = bus.rows("tally", "train")
    if not len(rows):
        return None
    return {k: int(v) for k, v in zip(COLUMNS, rows[-1])}
