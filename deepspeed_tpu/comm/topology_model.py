"""Analytic interconnect model: per-link peak bandwidth and bus-bandwidth
accounting for the collective-tracing plane.

The comm spans in ``comm/comm.py`` record how many bytes a collective moved
and how long the verb took; this module supplies the *denominator* — what
the link could have moved — so telemetry can report achieved bus bandwidth
as a fraction of peak.  Two tables:

* :data:`LINK_PEAK_GBPS` — per-chip ICI injection bandwidth by TPU
  generation (uni-directional, GB/s) plus a DCN fallback.  These are the
  analytic ceilings the nccl-tests-style busbw numbers are compared
  against (EQuARX frames quantized-collective wins exactly in these
  terms, which is why ROADMAP item 3 hooks in here).
* :data:`PEAK_TFLOPS` — per-chip bf16 dense peak, used by the engine's
  ``train/mfu`` gauge (analytic model flops / step time / peak).

Bus-bandwidth factors follow the nccl-tests convention (identical to
``benchmarks/communication.py``): an all-reduce moves ``2(n-1)/n`` of its
payload per link, gather/scatter families ``(n-1)/n``, rooted ops 1.0 —
so ``busbw = bytes/duration * factor`` is comparable across ops and world
sizes.

Everything here is host-side arithmetic over static tables: safe to call
at trace time, from the aggregator, or from a report script.
"""

# per-chip ICI link peak, uni-directional GB/s (1 GB = 1e9 bytes).
# Substring-matched against jax's Device.device_kind, first hit wins —
# longer/more-specific keys first.
LINK_PEAK_GBPS = (
    ("v6e", 180.0), ("v6 lite", 180.0), ("v6", 180.0),
    ("v5p", 200.0), ("v5e", 100.0), ("v5 lite", 100.0), ("v5", 200.0),
    ("v4", 100.0), ("v3", 70.0), ("v2", 62.5),
)

# cross-host data-center network fallback (per-host NIC, GB/s)
DCN_PEAK_GBPS = 12.5

# bf16 dense peak (TFLOP/s) of one JAX device; MFU = achieved model
# flops/s / (peak * device count).  Source, for this table and
# HBM_PEAK_GBPS: Google Cloud documentation, the "TPU v2" ... "TPU v6e"
# pages under cloud.google.com/tpu/docs (per chip; v2 and v3 halved,
# their chips being two devices).  The package's only copy: the train
# suite of ds_bench and the engine's train/mfu gauge both read it here.
PEAK_TFLOPS = (
    ("v6e", 918.0), ("v6 lite", 918.0), ("v6", 918.0),
    ("v5p", 459.0), ("v5e", 197.0), ("v5 lite", 197.0), ("v5", 459.0),
    ("v4", 275.0), ("v3", 61.5), ("v2", 22.5),
)

# per-chip HBM peak bandwidth (GB/s) — the denominator of the live
# bandwidth roofline (monitor/profiling.py roofline/*/bandwidth_frac):
# achieved bytes/s over a span divided by what the memory system could
# have streamed
HBM_PEAK_GBPS = (
    ("v6e", 1640.0), ("v6 lite", 1640.0), ("v6", 1640.0),
    ("v5p", 2765.0), ("v5e", 819.0), ("v5 lite", 819.0), ("v5", 2765.0),
    ("v4", 1228.0), ("v3", 900.0), ("v2", 700.0),
)


def _lookup(table, kind):
    k = (kind or "").lower()
    for key, val in table:
        if key in k:
            return val
    return None


def _device_kind():
    try:
        import jax
        return jax.local_devices()[0].device_kind
    except Exception:
        return None


def busbw_factor(op_name, world):
    """nccl-tests bus-bandwidth factor: scales algorithmic bandwidth
    (bytes/duration) to per-link traffic so ops are comparable."""
    n = max(2, int(world or 2))
    if op_name == "all_reduce":
        return 2.0 * (n - 1) / n
    if op_name in ("all_gather", "reduce_scatter", "all_to_all"):
        return (n - 1) / n
    return 1.0  # broadcast / scatter / ppermute / barrier


def link_peak_gbps(device_kind=None, cross_host=False):
    """Analytic per-link peak for the current (or named) device kind;
    DCN fallback when the transfer crosses hosts or the kind is unknown
    off-TPU.  None when nothing sensible is known (CPU test meshes)."""
    if cross_host:
        return DCN_PEAK_GBPS
    return _lookup(LINK_PEAK_GBPS, device_kind or _device_kind())


def device_peak_flops(device_kind=None):
    """Per-chip bf16 dense peak in FLOP/s (not TFLOP/s); None off-TPU."""
    tf = _lookup(PEAK_TFLOPS, device_kind or _device_kind())
    return tf * 1e12 if tf is not None else None


def hbm_peak_gbps(device_kind=None):
    """Per-chip HBM peak bandwidth in GB/s; None off-TPU (the live
    bandwidth roofline simply doesn't emit without a known peak)."""
    return _lookup(HBM_PEAK_GBPS, device_kind or _device_kind())


def bus_bandwidth(op_name, size_bytes, dur_ms, world, device_kind=None,
                  cross_host=False):
    """(busbw_gbps, peak_gbps) for one timed collective.

    ``busbw`` is algorithmic bandwidth (payload bytes / wall duration)
    scaled by the op's bus factor; ``peak`` is the analytic link ceiling
    (None when unknown — achieved bandwidth still reports).  Returns
    (None, peak) when the sample carries no usable duration."""
    peak = link_peak_gbps(device_kind=device_kind, cross_host=cross_host)
    if not dur_ms or dur_ms <= 0.0 or not size_bytes:
        return None, peak
    algbw = float(size_bytes) / (float(dur_ms) / 1e3)   # bytes/s
    return algbw * busbw_factor(op_name, world) / 1e9, peak
