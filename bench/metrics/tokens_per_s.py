"""Output tokens generated in the window over its seconds: each admitted
request's first token (from its prefill) plus, for every decode round, its
steps times the slots live in it. Partly served requests count."""


def read(run):
    first = sum(len(w.members) for w in run.waves)
    decoded = sum(r.n_steps * int(r.live.sum()) for r in run.rounds)
    return (first + decoded) / run.window_s
