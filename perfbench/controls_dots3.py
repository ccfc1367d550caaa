"""The controls of ``serve-mixed-window``'s comparison: the reference of
``perfbench/reference_dots3.py`` with ONE fault planted
(``reference_dots3.FAULTS``, each a branch of the reference itself), for
``drivers/serve_window.py reference_check`` to hold the engine's timed
programs against (``perfbench/controls_sarvam.py``'s reason: a program is
as far from a wrong reference as a wrong program is from the right one,
so every fault here has to come out as NOT correct, by the driver's own
verdicts).

- ``fp8_latent_rows``: the cached rows ``[c_kv | k_r]`` of both kinds of
  layer rounded to float8 (e4m3), the nearest precision below the bf16
  the configuration states for the cache;
- ``no_gate``: the head gates left out;
- ``no_rescale``: ``apply_mla_qkv_lora_rescale`` ignored;
- ``thetas_swapped``: full layers rotate at 5e4, sliding layers at 8e7;
- ``window_1026``: a sliding layer's query sees twice the window;
- ``stale_ring_block``: a ring's last block never written again behind the
  first wrap: a query finds the rows of 1 024 positions earlier there (a
  WRAP made them stale);
- ``warm_start_without_window_rows``: a request behind a document finds
  zeros where the document's last 512 rows belong in the sliding layers;
- ``no_selection``: a full layer's query attends to every key behind it;
- ``no_shared_expert``.

On the chip: ``PERFBENCH_CONTROLS=1 python3 perfbench/run.py --workload
serve-mixed-window ...`` adds ``checks.controls`` to the run's
``perfbench detail`` line (a reference pass a watched request and
control; readings only, the run's ``correct`` is its own).  On the CPU
``tests/test_dots3_serving.py`` plants each at tiny sizes.
"""

from __future__ import annotations

import os

from perfbench.reference_dots3 import FAULTS

VERDICTS = ("logits_match_reference", "tokens_match_reference",
            "selection_matches_reference", "full_output_matches_reference",
            "window_output_matches_reference",
            "warm_window_matches_reference")
NUMBERS = ("logit_rms_p90", "logit_rms_worst", "logit_abs_worst",
           "token_deficit_p90", "token_deficit_worst",
           "selection_overlap_min", "selection_overlap_mean",
           "full_out_rel", "full_out_rel_selected", "window_out_rel",
           "window_warm_rel")


def summary(checks: dict) -> dict:
    """The verdicts and the numbers they were made from."""
    out = {k: checks.get(k) for k in VERDICTS}
    out["correct"] = all(out.values())
    out.update({k: checks.get(k) for k in NUMBERS})
    return out


def readings(ctx, check) -> dict:
    """``check(fault)`` under every fault (or, where ``PERFBENCH_CONTROLS``
    names some, comma-separated, under those)."""
    wanted = os.environ.get("PERFBENCH_CONTROLS", "").split(",")
    out = {}
    for name in [f for f in FAULTS if f in wanted] or FAULTS:
        ctx.say(f"control {name}")
        out[name] = summary(check(name))
    return out
