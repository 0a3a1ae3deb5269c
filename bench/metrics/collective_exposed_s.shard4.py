"""The part of ``collective_s.shard4`` during which no other op ran on
the same chip, per diagram, averaged over the chips: the collective time
that compute did not hide."""

from bench import collectives


def read(run):
    return collectives.seconds(run, exposed=True)
