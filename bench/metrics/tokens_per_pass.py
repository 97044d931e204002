"""tokens_per_pass (decode loop, core/session.py): tokens committed per
occupied, unfinished slot per target pass, over the window's chunks."""


def read(ctx):
    chunks = ctx.window.probe.chunks
    slot_passes = sum(c.passes * c.active for c in chunks)
    if slot_passes == 0:
        return None
    return sum(c.tokens for c in chunks) / slot_passes
