"""The gates and taps of the gated short convolutions against the chip's
HBM peak: the bytes they NEED in the traced steps
(``perfbench/kernels_conv.py``: forward and its rematerialised twin read 3
x channels and write channels a token and layer, the backward reads the
incoming gradient and what the forward read and writes 3 x channels, the
taps' gradient once) over peak bytes/s x the device time under the
program's ``conv_mix`` scope.  Bound by memory: no matmul is in the scope.
The same bytes whatever implements the pass, so the share says how far
what runs is from one fused pass each way.  Sizes come from the run's
``shapes`` (``conv_layers``, ``conv_taps``, ``conv_channels``,
``act_bytes``); without them, without the scope, or in a rehearsal on the
CPU (no device, no peak), nothing."""

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"


SCOPES = ("conv_mix",)


def read(run):
    from perfbench.device_scopes import ms_per_step, of_run
    from perfbench.kernels_conv import conv_mix_step_bytes
    from perfbench.peaks import peaks_for

    sh = run.get("shapes", {})
    reduced = of_run(run)
    if "conv_layers" not in sh or reduced is None or reduced["rehearsal"]:
        return None
    ms = ms_per_step(run, SCOPES)
    if not ms:
        return None
    needed = conv_mix_step_bytes(
        sh["seq"] * sh["rows"] // run["chips"], sh["conv_channels"],
        sh["act_bytes"], sh["conv_taps"], sh["conv_layers"], sh["remat"])
    peak = peaks_for(run["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * needed / peak / (ms * 1e-3)
