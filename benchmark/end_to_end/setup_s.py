"""``setup_s``: process start (the first line of benchmark/run.py) to the
first timed instant of the window: boot of the cluster or server, weights
from the seed, compilation or cache loads, warm-up of the cell's shapes."""


def read(facts):
    return facts.get("setup_s")
