"""Device seconds per diagram of the collective ops (collective-permute,
all-reduce, all-gather, ...: the ring halo exchange of the shardmap
front-end), averaged over the chips: each asynchronous collective from
its start op to its done op, each synchronous one as its op."""

from bench import collectives


def read(run):
    return collectives.seconds(run, exposed=False)
