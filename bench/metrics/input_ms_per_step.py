"""Host time per window step spent making and placing the batch (the
``input`` span: tokens, Eqn 4 sample weights or rates, and the transfer's
enqueue), mean over the window's steps, in ms."""


def read(run):
    t = run.spans.get("input")
    if not t:
        return None
    return sum(t) / len(t) * 1e3
