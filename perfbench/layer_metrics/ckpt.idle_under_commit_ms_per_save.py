"""Milliseconds the device stood idle while the writer thread had a
``dlrover.ckpt.commit`` span open, per commit in the traced save period:
every idle interval of the device (the few milliseconds between two
plain steps too) cut to the commits' spans, over their number.  What a
save costs the device, seen from inside the program; from outside it is
``ckpt.lost_ms_per_save``.

The profiler drops a span that is still open when the session closes, so
a commit cut by the end of the traced window is known by its finished
parts (which tile it: lock wait, D2H, shm alloc, copy, publish)."""

LAYER = "checkpoint"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"

COMMIT = "dlrover.ckpt.commit"
FIRST_PART = "dlrover.ckpt.lock_wait"
PARTS = (FIRST_PART, "dlrover.ckpt.d2h_dispatch", "dlrover.ckpt.d2h_wait",
         "dlrover.ckpt.shm_alloc", "dlrover.ckpt.shm_copy",
         "dlrover.ckpt.publish")


def read(run):
    from perfbench import program_spans as ps

    parsed = ps.of_run(run)
    if parsed is None:
        return None
    commits = max(len(ps.named(parsed, COMMIT)),
                  len(ps.named(parsed, FIRST_PART)))
    if not commits:
        return None
    return ps.idle_under(parsed, COMMIT, *PARTS) / commits * 1e3
