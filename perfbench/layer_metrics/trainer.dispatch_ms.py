"""Milliseconds the training thread spends inside the call of the jitted
step until it returns (``dlrover.trainer.dispatch``: the enqueue, and
whatever the runtime makes the caller wait for), median over the traced
steps after which no save was due.  The rest of ``trainer.step_ms`` is the
caller waiting for the step's result."""

LAYER = "trainer"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"


def read(run):
    from perfbench import program_spans as ps

    parsed = ps.of_run(run)
    if parsed is None:
        return None
    # a due save follows its step on the same thread: the dispatch that
    # ended last before it is that step's
    ends = sorted(s + d for _, s, d, _ in
                  ps.named(parsed, "dlrover.trainer.dispatch"))
    saved = set()
    for _, s, _, attrs in ps.named(parsed, "dlrover.trainer.maybe_save"):
        before = [e for e in ends if e <= s]
        if attrs.get("due") and before:
            saved.add(before[-1])
    return ps.median_ms(parsed, "dlrover.trainer.dispatch",
                        keep=lambda line, s, d, a: s + d not in saved)
