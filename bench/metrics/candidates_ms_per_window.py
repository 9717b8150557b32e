"""Host milliseconds of candidate generation (``mine.candidates`` spans:
level 2 from the frequent event types, then the Apriori join of each
level) per window mined."""

from window_spans import per_window_ms


def read(run):
    return per_window_ms(run, "mine.candidates")
