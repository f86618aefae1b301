"""Engine (the cache): the bytes of the paged pools that hold a live position, over the
live positions, when the timed window closed: each pool group's blocks that
its slots' lengths reach (``blocks_live`` of
``serve/kv_cache.py:PagedKVCache.group_facts``: every block up to a slot's
length in a group that grows, at most the ring's ``ceil(window / bs) + 1``
in a window group; the scheduler's reservation for tokens to come is not
in it) times the group's block bytes, summed, over the sum of the slots'
lengths. A cache that keeps every position of every layer reads the
model's bytes a token; a window layer that keeps its window reads less
the longer the contexts are. Nothing for a program without pool groups."""


def read(ctx):
    groups = ctx.facts.get("pool_groups")
    if not groups:
        return None
    live = sum(g["live_tokens"] for g in groups.values()) / len(groups)
    held = sum(g["blocks_live"] * g["block_bytes"] for g in groups.values())
    return held / live if live else None
