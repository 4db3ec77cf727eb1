"""Padded images over the images sent to the device: each request of the
traced window pads to the program's batch bucket (``CompiledModel.buckets``,
the smallest bucket that holds it).  A count, in percent."""


def read(ctx):
    sent = sum(ctx.program_bucket(r.size) for r in ctx.sent)
    if not sent:
        return None
    return 100.0 * (sent - sum(r.size for r in ctx.sent)) / sent
