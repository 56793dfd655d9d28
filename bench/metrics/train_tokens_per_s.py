"""Tokens trained per second over all of the cell's chips: the tokens of
every step completed in the window over the time from the window's start
(the first step's start) to the last step's end (host clock)."""


def read(run):
    return run.tokens_per_s
