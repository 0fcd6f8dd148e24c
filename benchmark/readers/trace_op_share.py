"""Device time of the HLO ops whose name starts with ``prefix`` (their own
time, nested ops taken out) over device busy time, in percent."""


def read(ctx, prefix):
    trace = ctx["trace"]
    if trace is None or not trace.busy_s or not trace.op_self_s:
        return None
    own = sum(s for name, s in trace.op_self_s.items()
              if name.startswith(prefix))
    return 100.0 * own / trace.busy_s
