"""Share of the traced window in which a collective ran on a device while no
other operation did, averaged over the devices, in percent. Nothing to read
where the trace holds no collective (one chip)."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    total, exposed = tr.collective_seconds()
    if total <= 0:
        return None
    return 100.0 * exposed / tr.window_s
