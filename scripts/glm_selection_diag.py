#!/usr/bin/env python3
"""Can ``chipbench``'s reference check judge a model that SELECTS its keys?

``serve_cell`` compares the logits of two independent forwards (the
program in bf16, the reference in float32).  A ``glm_moe_dsa`` model
attends, in every layer, to the ``index_topk`` keys of largest index
score; two forwards that are not bit-equal order the keys at that boundary
differently, attend to other keys, and on seeded weights (attention near
uniform over a few thousand keys) the logits move by many times
``LOGIT_TOL`` with neither side wrong.  This tool measures that, through
the harness's own comparison, on the check's own prompts:

  own       the program against the reference on the reference's OWN
            selection: what ``correct`` reads today;
  shared    the program against the reference made to attend to the keys
            THE PROGRAM selected (every layer, every token of the prompts
            and of the decoded rows): what is left is the arithmetic;
  swaps     per query and layer, how many of the program's selected keys
            the reference's own index scores (on the shared trajectory)
            would have left out;
  noise     (``--noise x``) the reference against ITSELF with Gaussian
            noise of ``x`` standard deviations of a row's index scores
            added before its top-k: the model's own sensitivity, no
            program involved.

``--mantissa-bits 3`` is ``chipbench/control.py``'s road under both
readings: the program serves weights rounded to so many bits of mantissa
(in place: the sound and the rounded set do not fit one chip together),
the reference keeps the weights as seeded.  A comparison that can judge
reads the control far over ``LOGIT_TOL`` and a sound run under it.

``--reference-only`` leaves the program out altogether and needs no chip
(full width on the CPU: about 3 minutes a forward): the reference's rows
of one prompt (the mix's shortest check length and ``--rows`` more tokens)
against its own rows after its selection was disturbed: ``--noise x`` as
above, then a selection of RANDOM causal keys, then the selection less one
page of 128 keys.  It says what a check of two independent forwards can
tell on this configuration's seeded weights: the first has to read well
under ``LOGIT_TOL``, the second over it.  ``--embedding-std s`` overrides
the file's ``seeded_weights.embedding_std`` (0 = the program's default,
rows of norm 1) to read what the choice does.

``--margins`` reads the routing rule on the chip (the reference's
``MARGIN``, which each expert layer scales by the root of its depth): the
check's prompts served as the harness serves them, and for each of the 72
rows its own token's margin at every expert layer (the reference's
``_route`` is wrapped to hand its scores out) beside the row's error.  For
each value of ``MARGINS`` in ``MARGIN``'s place: how many rows it would
leave decided and their largest error; and the largest margin, in the same
units, of any row over ``LOGIT_TOL``, which ``MARGIN`` has to clear.

The program is not given a switch: its ``topk_mask`` and ``topk_indices``
(deepspeed_tpu/ops/latent_attention.py) are wrapped, here, to hand what
they selected to the host through ``jax.debug.callback``; the reference's
``_selection`` is wrapped to return it.  The requests are served one at a
time so that every record belongs to the request at hand.

    python3 scripts/glm_selection_diag.py --seeds 1 2 [--noise 0.005]
        [--config chipbench/configs/glm-5-ep16.json]
        [--traffic longctx-closed] [--dtype bfloat16]

One JSON line a seed, the last line a summary.  Needs the chip at the real
size (``chiprun -- python3 scripts/glm_selection_diag.py ...``); the toy
configuration runs on the CPU (``--cpu``; tests/chipbench/test_glm_cell.py).
"""

import argparse
import functools
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


class ProgramSelection:
    """What the program's two selection functions picked, by layer and
    query position, while ``recording()`` is open."""

    def __init__(self, n_layers):
        self.n_layers = n_layers
        self.calls = 0              # trace-time: which layer is being traced
        self.prefill = [[] for _ in range(n_layers)]    # (positions, bits)
        self.decode = [{} for _ in range(n_layers)]     # position -> keys

    def clear(self):
        for rows in self.prefill:
            rows.clear()
        for rows in self.decode:
            rows.clear()

    def _next_layer(self):
        layer = self.calls % self.n_layers
        self.calls += 1
        return layer

    def _record_prefill(self, layer, mask, count):
        import numpy as np
        mask, count = np.asarray(mask), np.asarray(count)
        for b in range(mask.shape[0]):
            self.prefill[layer].append(
                (count[b] - 1, np.packbits(mask[b], axis=-1), mask.shape[-1]))

    def _record_decode(self, layer, idx, live, count):
        import numpy as np
        idx, live, count = np.asarray(idx), np.asarray(live), np.asarray(count)
        for b in range(idx.shape[0]):
            self.decode[layer][int(count[b]) - 1] = idx[b][live[b]]

    def recording(self):
        """Context manager: the program's selection functions wrapped."""
        import contextlib
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.ops import latent_attention as la

        @contextlib.contextmanager
        def wrapped():
            mask_fn, indices_fn = la.topk_mask, la.topk_indices

            def topk_mask(scores, valid, k):
                mask = mask_fn(scores, valid, k)
                jax.debug.callback(
                    functools.partial(self._record_prefill,
                                      self._next_layer()),
                    mask, jnp.sum(valid, axis=-1))
                return mask

            def topk_indices(scores, valid, k):
                idx, live = indices_fn(scores, valid, k)
                jax.debug.callback(
                    functools.partial(self._record_decode,
                                      self._next_layer()),
                    idx, live, jnp.sum(valid, axis=-1))
                return idx, live

            la.topk_mask, la.topk_indices = topk_mask, topk_indices
            try:
                yield self
            finally:
                la.topk_mask, la.topk_indices = mask_fn, indices_fn

        return wrapped()

    def masks(self, prompt_len, total, padded):
        """The program's selection of one request as the reference's
        ``[padded, padded]`` masks, a layer, and which rows are known:
        those under ``prompt_len`` from its prefill, those from there to
        ``total`` (the ids the reference is given) from its decode steps;
        the reference keeps its own selection in every other row."""
        import numpy as np
        out = []
        for layer in range(self.n_layers):
            mask = np.zeros((padded, padded), bool)
            known = np.zeros((padded,), bool)
            for positions, bits, width in self.prefill[layer]:
                rows = (positions >= 0) & (positions < prompt_len)
                cols = min(width, padded)
                mask[positions[rows], :cols] = np.unpackbits(
                    bits[rows], axis=-1, count=width)[:, :cols]
                known[positions[rows]] = True
            for position, keys in self.decode[layer].items():
                if prompt_len <= position < total:
                    mask[position, keys] = True
                    known[position] = True
            out.append((mask, known))
        return out


def _serve_one(engine, serve_cell, rid, ids, recorder):
    """One check request through the engine alone: (its ids [1, S + new -
    1], the logits rows the program sampled from, the masks recorded)."""
    import jax
    import numpy as np
    recorder.clear()
    done = {}
    with serve_cell._logits_rows(engine) as rows:
        engine.add_request(rid, ids,
                           max_new_tokens=serve_cell.CHECK_DECODE_TOKENS)
        while rid not in done:
            done.update(engine.step())
    jax.effects_barrier()
    return (np.asarray(done[rid], np.int32)[None, :-1], np.stack(rows[rid]))


def _noisy(exact, sigma):
    """``select`` after Gaussian noise of ``sigma`` of a row's spread."""
    import jax
    import jax.numpy as jnp

    def select(scores, causal, k):
        spread = jnp.std(scores, axis=-1, keepdims=True)
        return exact(scores + sigma * spread * jax.random.normal(
            jax.random.key(17), scores.shape), causal, k)
    return select


def _less_a_page(exact, first=1024, page=128):
    """``select`` less the keys of one page (which every query of the
    check's rows has in context)."""
    import jax.numpy as jnp

    def select(scores, causal, k):
        at = jnp.arange(scores.shape[-1])
        return exact(scores, causal, k) & ~((at >= first)
                                            & (at < first + page))
    return select


def _disturbed(ref, select):
    """Context manager: the reference selects through ``select``."""
    import contextlib
    import jax

    @contextlib.contextmanager
    def swapped():
        exact, ref.select = ref.select, select
        jax.clear_caches()      # ``select`` is traced into ``_selection``
        try:
            yield
        finally:
            ref.select = exact
            jax.clear_caches()
    return swapped()


def sensitivity(cell, seed, devices, noise, rows):
    """``--reference-only``: one seed's readings (module docstring)."""
    import numpy as np
    from chipbench import serve_cell, sut, traffic

    cfg, ref = cell.config, cell.reference
    model = sut.build_model(cell)
    params = sut.seeded_weights(model, seed,
                                sut.DTYPES[cfg["serve"]["dtype"]], devices)
    n = int(traffic.quantile_grid(cell.mix["prompt_tokens"],
                                  serve_cell.CHECK_PROMPTS)[0])
    ids = traffic.rng_for(seed, 5, 0).integers(
        0, cfg["vocab_size"], n + rows - 1, dtype=np.int32)[None]
    want, decided = serve_cell._reference_rows(cell, params, ids, rows)
    top = max(1.0, float(np.max(np.abs(want))))
    out = {"seed": int(seed), "prompt": n, "rows": rows,
           "rows_decided": int(decided.sum()),
           "embedding_std": float(np.std(np.asarray(
               params["tok_embed"], np.float32)))}
    for name, select in (("noise", _noisy(ref.select, noise)),
                         ("random_keys", _noisy(ref.select, 100.0)),
                         ("less_a_page", _less_a_page(ref.select))):
        with _disturbed(ref, select):
            got, decided_too = serve_cell._reference_rows(
                cell, params, ids, rows)
        both = decided & decided_too
        error = np.max(np.abs(got - want), axis=-1)[both] / top
        out[name] = {"rows_compared": int(both.sum()),
                     "logit_error": float(error.max()),
                     "median_row": float(np.median(error))}
    out["noise"]["sigma"] = noise
    return out


MARGINS = (0.002, 0.0025, 0.003, 0.0035)


def margins(cell, seed, devices):
    """``--margins``: one seed's readings (module docstring)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import deepspeed_tpu
    from chipbench import serve_cell, sut

    cfg, ref = cell.config, cell.reference
    model = sut.build_model(cell)
    dtype = cfg["serve"]["dtype"]
    params = sut.seeded_weights(model, seed, sut.DTYPES[dtype], devices)
    engine = deepspeed_tpu.init_inference(
        model=model, params=params, dtype=dtype).create_serving_engine(
        max_batch=int(cell.mix["max_batch"]), **cfg["serve"]["engine"])
    served = serve_cell._serve_check_prompts(cell, engine, seed)
    del engine
    gc.collect()

    route, layers, scales = ref._route, [], []

    def handing_out(h, wg, bias, sizes, margin):
        """The reference's routing, and each token's smallest margin in
        s + b between a chosen and an unchosen expert, one of them held
        here (what ``_route`` holds against its layer's ``margin``)."""
        biased = jax.nn.sigmoid(h @ wg.astype(jnp.float32)) \
            + bias.astype(jnp.float32)
        top = jax.lax.top_k(biased, sizes.per_token + 1)[0]
        kth, best_out = top[:, -2:-1], top[:, -1:]
        gap = jnp.where(biased >= kth, biased - best_out, kth - biased)
        held = jnp.arange(biased.shape[1]) < sizes.held
        layers.append(np.asarray(jnp.min(
            jnp.where(held, gap, jnp.inf), axis=-1)))
        scales.append(float(margin) / ref.MARGIN)
        return route(h, wg, bias, sizes, margin)

    ref._route = handing_out
    margin, error = [], []
    try:
        for ids, rows in served:
            del layers[:], scales[:]
            want, _ = serve_cell._reference_rows(cell, params, ids,
                                                 len(rows))
            # [layers, padded tokens] -> this prompt's rows
            margin.append(np.stack(layers)[
                :, ids.shape[1] - len(rows):ids.shape[1]])
            error.append(np.max(np.abs(rows - want), axis=-1)
                         / max(1.0, float(np.max(np.abs(want)))))
    finally:
        ref._route = route
    by_layer = np.concatenate(margin, axis=-1)      # [layers, rows]
    # in units of each layer's share of the rule: what MARGIN is held to
    scaled = (by_layer / np.asarray(scales)[:, None]).min(axis=0)
    error = np.concatenate(error)
    wrong = error > serve_cell.LOGIT_TOL
    return {
        "seed": int(seed), "rows": len(error), "layer_scales": scales,
        "largest_margin_of_a_row_over_tol":
            float(scaled[wrong].max()) if wrong.any() else None,
        "by_margin": {str(m): {
            "rows_decided": int(np.sum(scaled >= m)),
            "logit_error": float(error[scaled >= m].max())}
            for m in MARGINS},
        "per_row": {"error": [round(float(e), 4) for e in error],
                    "score_margin": np.round(by_layer.T, 5).tolist()}}


def diagnose(cell, seed, devices, noise=0.0, dtype=None, mantissa_bits=0):
    """The readings of one seed (module docstring)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import deepspeed_tpu
    from chipbench import serve_cell, sut, traffic

    cfg, ref = cell.config, cell.reference
    model = sut.build_model(cell)
    dtype = dtype or cfg["serve"]["dtype"]
    params = sut.seeded_weights(model, seed, sut.DTYPES[dtype], devices)
    if mantissa_bits:
        from chipbench.control import round_mantissa
        params = jax.jit(lambda tree: jax.tree_util.tree_map(
            lambda w: round_mantissa(w, mantissa_bits), tree),
            donate_argnums=0)(params)
    recorder = ProgramSelection(cfg["num_hidden_layers"])
    lengths = traffic.quantile_grid(cell.mix["prompt_tokens"],
                                    serve_cell.CHECK_PROMPTS)
    with recorder.recording():
        engine = deepspeed_tpu.init_inference(
            model=model, params=params, dtype=dtype).create_serving_engine(
            max_batch=int(cell.mix["max_batch"]), **cfg["serve"]["engine"])
        served, selections = [], []
        for i, n in enumerate(lengths):
            ids = traffic.rng_for(seed, 5, i).integers(
                0, cfg["vocab_size"], int(n), dtype=np.int32)
            served.append(_serve_one(engine, serve_cell, f"check-{i}", ids,
                                     recorder))
            total = served[-1][0].shape[1]
            selections.append(recorder.masks(
                int(n), total, total + (-total) % ref.BLOCK_Q))
    leaks = engine.leak_report()
    del engine
    if mantissa_bits:       # the reference keeps the weights as seeded
        del params
    gc.collect()            # the engine's pools, and the rounded weights
    if mantissa_bits:
        params = sut.seeded_weights(model, seed, sut.DTYPES[dtype], devices)

    def compare():
        checks = [serve_cell._compare_with_reference(cell, [one], params)
                  for one in served]
        decided = [c["logit_error"] for c in checks
                   if c["logit_error"] is not None]
        return {"logit_error": max(decided) if decided else None,
                "by_prompt": [c["logit_error"] for c in checks],
                "rows_compared": sum(c["rows_compared"] for c in checks),
                "rows": sum(c["rows_compared"] + c["rows_undecided"]
                            for c in checks)}

    own_selection = ref._selection
    out = {"seed": int(seed), "mantissa_bits": mantissa_bits,
           "prompts": [int(n) for n in lengths],
           "leaks": leaks, "own": compare()}

    swaps, state = [], {}

    def shared(h, c_q, w, positions, sizes):
        own = np.asarray(own_selection(h, c_q, w, positions, sizes))[0]
        theirs, known = state["masks"][state["layer"]]
        state["layer"] += 1
        # a query with more causal keys than index_topk: the selection bites
        bites = known & (np.arange(len(known)) >= sizes.topk)
        if bites.any():
            swaps.extend(np.sum(theirs[bites] & ~own[bites], axis=-1))
        return jnp.asarray(np.where(known[:, None], theirs, own))[None]

    ref._selection = shared
    try:
        checks = []
        for one, masks in zip(served, selections):
            state.update(masks=masks, layer=0)
            checks.append(serve_cell._compare_with_reference(
                cell, [one], params))
    finally:
        ref._selection = own_selection
    decided = [c["logit_error"] for c in checks
               if c["logit_error"] is not None]
    out["shared"] = {
        "logit_error": max(decided) if decided else None,
        "by_prompt": [c["logit_error"] for c in checks],
        "rows_compared": sum(c["rows_compared"] for c in checks)}
    out["swaps"] = {
        "queries": len(swaps),
        "mean": float(np.mean(swaps)) if swaps else 0.0,
        "max": int(np.max(swaps)) if swaps else 0,
        "of": int(cfg["index_topk"])}

    if noise:
        errors = []
        for ids, rows in served:
            want, decided = serve_cell._reference_rows(
                cell, params, ids, len(rows))
            with _disturbed(ref, _noisy(ref.select, noise)):
                got, decided_noisy = serve_cell._reference_rows(
                    cell, params, ids, len(rows))
            both = decided & decided_noisy
            if both.any():
                errors.append(float(np.max(np.abs(got - want)[both]))
                              / max(1.0, float(np.max(np.abs(want)))))
        out["noise"] = {"sigma": noise,
                        "logit_error": max(errors) if errors else None}
    return out


def _in_a_process_of_one_device(argv):
    """``--margins --cpu`` from a process that was given several CPU
    devices (the tests' eight): the same command in a child with one, as
    the cell's own command runs.  A float32 toy's rows move in the
    seventh digit with the number of devices the host's threads are
    shared among, and this reading is held to the harness's own."""
    import subprocess
    done = subprocess.run(
        [sys.executable, os.path.abspath(__file__)] + list(argv),
        env=dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=""),
        capture_output=True, text=True, check=True)
    readings = [json.loads(line) for line in done.stdout.splitlines()
                if line.startswith("{")]
    for reading in readings:
        print(json.dumps(reading), flush=True)
    return readings


def main(argv=None, require_tpu=True):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=os.path.join(
        ROOT, "chipbench", "configs", "glm-5-ep16.json"))
    ap.add_argument("--traffic", default="longctx-closed")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--noise", type=float, default=0.0)
    ap.add_argument("--dtype", default=None)
    ap.add_argument("--mantissa-bits", type=int, default=0)
    ap.add_argument("--cpu", action="store_true",
                    help="do not insist on a TPU (toy configurations; "
                    "--reference-only at any size)")
    ap.add_argument("--reference-only", action="store_true")
    ap.add_argument("--margins", action="store_true")
    ap.add_argument("--rows", type=int, default=96)
    ap.add_argument("--embedding-std", type=float, default=None)
    args = ap.parse_args(argv)
    from chipbench import cells, device, serve_cell, traffic
    with open(args.config) as f:
        config = json.load(f)
    if args.embedding_std is not None:
        config["seeded_weights"] = {
            "embedding_std": args.embedding_std or None}
    if os.path.exists(args.traffic):     # a file, or a name of chipbench's
        with open(args.traffic) as f:
            mix = json.load(f)
    else:
        mix = traffic.load_mix(args.traffic)
    cell = cells.Cell(name="diag", chips=1, config=config, mix=mix,
                      end_to_end=[], per_layer=[])
    if not args.cpu:    # the compile cache as run.py keeps it
        import jax
        from deepspeed_tpu.utils.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = device.require_devices(1, require_tpu and not args.cpu)
    readings = []
    if args.margins and args.cpu and len(devices[0].client.devices()) > 1:
        return _in_a_process_of_one_device(
            sys.argv[1:] if argv is None else argv)
    if args.reference_only or args.margins:
        for seed in args.seeds:
            readings.append(
                margins(cell, seed, devices) if args.margins else
                sensitivity(cell, seed, devices, args.noise or 0.005,
                            args.rows))
            print(json.dumps(readings[-1]), flush=True)
        return readings
    for seed in args.seeds:
        readings.append(diagnose(cell, seed, devices, args.noise,
                                 args.dtype, args.mantissa_bits))
        print(json.dumps(readings[-1]), flush=True)
    summary = {
        "config": config["name"], "logit_tol": serve_cell.LOGIT_TOL,
        "mantissa_bits": args.mantissa_bits,
        "own": [r["own"]["logit_error"] for r in readings],
        "shared": [r["shared"]["logit_error"] for r in readings],
        "swaps_mean": [r["swaps"]["mean"] for r in readings]}
    print(json.dumps(summary), flush=True)
    return readings


if __name__ == "__main__":
    main()
