"""Host time in ``CompiledModel.run`` up to its return, before the result
is awaited: padding, the jitted call's dispatch, the slice.  Mean per
request of the traced window, in milliseconds (host clock)."""


def read(ctx):
    if not ctx.sent:
        return None
    return 1e3 * sum(r.t_dispatched - r.t_send for r in ctx.sent) / len(ctx.sent)
