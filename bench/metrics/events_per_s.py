"""Events of every window due inside the sending time (replay: sent in
it), over the measured window: from its start to the arrival of the last
of their deltas."""


def read(run):
    return sum(r.n_events for r in run.delivered()) / run.seconds
