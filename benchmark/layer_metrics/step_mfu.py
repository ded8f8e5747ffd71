"""``step_mfu`` — compiled step: the whole step's share of the chip's peak.
The family's FLOPs per item times the traced run's own items per second per
chip, over the bfloat16 peak of ``peaks.json``."""


def read(run):
    peaks, window = run.get("peaks"), run["window"]
    if not peaks or not window["seconds"] or not window["items"]:
        return None
    flops = run["family"].flops_per_item(run["config"], run["size"])
    rate = window["items"] / window["seconds"] / window["chips"]
    return 100.0 * flops * rate / peaks["bf16_flops_per_s"]
