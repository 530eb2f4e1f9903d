"""The widest q-gram set width of this cell's workload when the window
ended (``duke_device_set_width{workload,property}`` in the ``/metrics``
page taken after it; engine/device_matcher.py sizes each QGram property's
width from its values).  The intersection kernel compares width x width
slots per pair.  None where the program keeps no such gauge."""


def read(ctx):
    workload = ("workload", ctx.service["name"])
    widths = [value for (family, labels), value in ctx.after.items()
              if family == "duke_device_set_width" and workload in labels]
    return max(widths) if widths else None
