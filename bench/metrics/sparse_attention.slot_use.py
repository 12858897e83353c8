"""Share of the tile slots that the sparse sweep's grid walks which hold
a tile: engine counters ``sparse_slots_used`` over
``sparse_slots_visited`` for the window's prefills (anchor slots count
as used)."""


def read(ctx):
    if "sparse_slots_visited" not in ctx.stats1:
        return None
    visited = ctx.delta("sparse_slots_visited")
    if visited <= 0:
        return None
    return 100.0 * ctx.delta("sparse_slots_used") / visited
