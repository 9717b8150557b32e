"""Mean duration of the scheduler's steps (``schedule.step`` spans) that
ended inside the measured window."""


def read(run):
    d = run.span_durations("schedule.step")
    return sum(d) / len(d) * 1e3 if d else None
