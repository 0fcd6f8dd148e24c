"""Share of the window's wall spent inside a harness span, in percent."""


def read(ctx, span):
    if span not in ctx["spans"] or not ctx["window_s"]:
        return None
    return 100.0 * ctx["spans"][span] / ctx["window_s"]
