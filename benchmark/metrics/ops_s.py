"""Operations acknowledged inside the window per second of the window;
a read counts when its value came back."""

UNIT = "ops/s"


def read(run):
    return run.acked / run.window_s if run.window_s > 0 else None
