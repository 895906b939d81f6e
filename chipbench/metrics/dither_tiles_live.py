"""dither_tiles_live: the share of the 128 x 128 tiles of the quantized
cotangents that hold a non-zero level, over every kernel-variant projection
of the window's last step, in %. The int8 matmuls skip the other tiles.

Read from the program's counter: ``Trainer.fit`` records the last step's
tally (``repro.core.dithered.TALLY_FIELDS``) on its metrics bus as the
``tally`` stream; a program without it reads nothing.
"""
from chipbench import tally


def read(ctx):
    row = tally.last()
    if row is None or row["tiles"] == 0:
        return None
    return 100.0 * row["tiles_live"] / row["tiles"]
