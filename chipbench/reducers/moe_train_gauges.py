"""What a trainer of a dropless expert model counts of its own step, read
from the program's gauges (``train/moe/expert_pairs``,
``train/moe/expert_load_max``, ``train/moe/expert_rows``: outputs of the
jitted train step, summed over the expert layers and the micro-batches of
one optimizer step, the load's maximum kept; docs/telemetry.md).  A gauge
holds its newest value, so these are the last step's: every step of a cell
routes as many pairs to within a fraction of a percent (uniform tokens).
None where the program sets no such gauges (a commit from before them, or
telemetry off).

``what``: ``load_ratio`` (the fullest held expert of any layer and
micro-batch over the mean load of a held expert), ``pad_pct`` (rows the
grouped product computed beyond the pairs, tile padding, in % of the
rows)."""

NAMES = ("expert_pairs", "expert_load_max", "expert_rows")


def gauges():
    """{name: the last step's value} or None."""
    from deepspeed_tpu.monitor.telemetry import get_telemetry
    registry = getattr(get_telemetry(), "registry", None)
    if registry is None:
        return None
    held = registry.snapshot().get("gauges", {})
    found = {n: held["train/moe/" + n]["value"] for n in NAMES
             if "train/moe/" + n in held}
    return found if len(found) == len(NAMES) and found["expert_rows"] else None


def read(run, what):
    found = gauges()
    if found is None:
        return None
    if what == "pad_pct":
        return 100.0 * (found["expert_rows"] - found["expert_pairs"]) \
            / found["expert_rows"]
    if what == "load_ratio":
        calls = run.model["n_layers"] * run.model["gas"]
        mean = found["expert_pairs"] / (calls * run.config["num_experts"])
        return found["expert_load_max"] / mean if mean else None
    raise ValueError(f"moe_train_gauges: unknown quantity {what!r}")
