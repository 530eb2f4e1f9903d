"""Share of the corpus capacity the device scorer scanned inside the
window, %: rows scanned over capacity, summed over its calls
(``duke_device_scan_rows_total{part}``, engine/device_matcher.py
``dispatch_block``).  None where the program keeps no such counter."""


def read(ctx):
    capacity = ctx.delta("duke_device_scan_rows_total", part="capacity")
    if capacity <= 0:
        return None
    return 100.0 * ctx.delta("duke_device_scan_rows_total",
                             part="scanned") / capacity
