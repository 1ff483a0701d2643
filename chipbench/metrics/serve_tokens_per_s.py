"""Every output token timestamped inside the window, over the window.  The
window runs from the engine's clock zero to its close, the end of the
first decode tick that ends after ``--seconds``, so that it holds whole
ticks: every token and every second of it count."""


def read(run):
    n = sum(1 for r in run.results for t in r.token_times_s if t <= run.close_s)
    return n / run.close_s
