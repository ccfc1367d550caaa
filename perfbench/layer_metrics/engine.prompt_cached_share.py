"""Percent of the admitted prompts' tokens that their prefill began
BEHIND: 100 x the sum of ``cached_tokens`` over the sum of
``prompt_tokens`` of the ``dlrover.request.admitted`` events of the traced
window (``serving/engine.py _book_admission``; what
``EngineStats.prompt_tokens_cached / prompt_tokens`` is over the engine's
life).  ``cached_tokens`` is where a chunked warm start's cursor began
(``paged.warm_start``: the last chunk boundary inside the shared blocks),
or the shared region whose writes a bucketed prefill masks, or 0 for a
cold start: the document cells exist because six cached documents of
16-30 k tokens are not prefilled again, and this says how much of it
holds; unique prompts read 0.

A share, filed under the one serving end-to-end metric there is; it is
what keeps ``engine.prefill_wall_ms``, and so the first-token tail, short
in those cells.  Fewer than 3 admissions in the window, or a program
that writes no such event (the parent of PR 52), report nothing."""

LAYER = "engine"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_span"

EVENT = "dlrover.request.admitted"


def read(run):
    from perfbench import program_spans as ps

    parsed = ps.of_run(run)
    admitted = [a for _, _, _, a in ps.named(parsed, EVENT)
                if "prompt_tokens" in a] if parsed else []
    prompts = sum(int(a["prompt_tokens"]) for a in admitted)
    if len(admitted) < 3 or not prompts:
        return None
    return 100.0 * sum(int(a["cached_tokens"]) for a in admitted) / prompts
