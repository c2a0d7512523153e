"""Steps of the window that the trainer did not apply (skipped by its
non-finite guard), from its step outcomes. Layer: trainer."""


def read(ctx):
    return ctx.get("nonapplied")
