"""Share of the traced window in which no op ran on the device (%).

Busy is the union of the device's op intervals in the window, averaged
over the chips the cell uses; the window runs from the first
``bench.next_batch`` span to the end of ``bench.block_until_ready``.
"""


def read(ctx):
    red = ctx["trace"]
    if not red.devices or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
