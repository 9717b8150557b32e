"""Mean duration of one session's window mining (``session.mine_window``
spans: candidate generation, the carried counters, the exact recount)
that ended inside the measured window."""


def read(run):
    d = run.span_durations("session.mine_window")
    return sum(d) / len(d) * 1e3 if d else None
