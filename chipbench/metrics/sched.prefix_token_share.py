"""Share of prompt tokens the radix prefix index served, over the
requests admitted in the window: sum of `n_prefix_tokens` (the shared
tokens the scheduler attached) over the sum of prompt tokens, in %."""


def read(run):
    w = run.window
    adm = [t for t in w.tracks if t.t_admit and w.t0 <= t.t_admit < w.t1]
    total = sum(len(t.prompt) for t in adm)
    if not total:
        return None
    return 100.0 * sum(t.n_prefix for t in adm) / total
