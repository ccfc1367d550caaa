"""Percent of the attention kernel's tiles of queries, over the prompt
chunks of the traced window, that held a real query: the sum of
``query_tiles_live`` over the sum of ``query_tiles`` on the window's
``dlrover.engine.prefill_chunk`` spans (host arithmetic of
``serving/engine.py _book_key_blocks``: a chunk program has a fixed number
of queries, and behind a prompt's last token they are padding).  Since
PR 42 ``mla_prefill_attn`` walks no key block for a tile without a real
query, so this is the share of the masked-dense attention's work a chunk
still does; the rest of the chunk (projections, index scan, MLPs)
computes the padding regardless.  A property of the traffic and the
chunk size, read where the work happens; a program that does not book
the tiles (the parent of PR 42) reports nothing.

One body for every ``engine.prefill_live_tile_share.<suffix>``."""

LAYER = "engine"
UNIT = "%"
BETTER = "lower"
SOURCE = "program_span"

CHUNK = "dlrover.engine.prefill_chunk"


def read(run):
    from perfbench import program_spans as ps

    parsed = ps.of_run(run)
    if parsed is None:
        return None
    # a step's first program opens the name twice: its wait carries the
    # attributes, its dispatch none
    booked = [a for _, _, _, a in ps.named(parsed, CHUNK)
              if "query_tiles" in a]
    tiles = sum(int(a["query_tiles"]) for a in booked)
    if not tiles:
        return None
    return 100.0 * sum(int(a["query_tiles_live"]) for a in booked) / tiles
