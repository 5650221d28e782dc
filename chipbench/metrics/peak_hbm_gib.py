"""The device allocator's ``peak_bytes_in_use`` at the end of the window,
on the fullest of the cell's chips, in GiB."""


def read(ctx):
    if ctx.memory_peak_bytes is None:
        return None
    return ctx.memory_peak_bytes / 2**30
