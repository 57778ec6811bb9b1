"""Generated tokens delivered inside the window, over the window's seconds
(host clock; the window opens with every slot busy and closes with the
first step delivered ``--seconds`` later, whose tokens count)."""


def compute(run):
    n = sum(1 for _ in run.deliveries())
    return n / (run.close_t - run.open_t)
