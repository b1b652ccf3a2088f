"""KV cache: share of the rows the cache holds that the decode ticks attend
over, in %.  Each tick's live rows are the harness's ``ctx_sum`` (the rows of
every decoding slot, the tick's new row included: the engine's
``kv_live_rows`` counter); the rows held are the same every tick (the
engine's ``kv_cache_rows``): ``max_batch x max_seq`` for the dense cache and
``n_blocks x block_size`` for a paged pool, which the engine sizes at one
null block plus ``max_seq / block_size`` blocks a slot."""


def rows_held(serve: dict) -> int:
    """Rows of the KV cache, live or not, as the engine sizes it."""
    B, S = int(serve["max_batch"]), int(serve["max_seq"])
    if not serve["paged"]:
        return B * S
    bs = int(serve["block_size"])
    return (1 + B * (S // bs)) * bs


def read(run):
    ticks = [s for s in run.steps if s.decoded]
    if not ticks:
        return None
    return 100.0 * sum(s.ctx_sum for s in ticks) / (
        len(ticks) * rows_held(run.serve))
