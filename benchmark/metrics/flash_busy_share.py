"""Share of the device's busy time that the flash attention kernels take, in
percent (every event whose name holds ``mxtpu_flash``)."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    secs, calls = tr.kernel_seconds("mxtpu_flash")
    busy = tr.busy_s()
    return 100.0 * secs / busy if calls and busy > 0 else None
