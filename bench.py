"""Benchmark: BERT-base pretraining throughput on the attached device.

Prints ONE JSON line:
  {"metric": "bert_base_tokens_per_sec_per_chip", "value": N,
   "unit": "tokens/s/chip", "vs_baseline": MFU/0.35, ...}

The baseline is the driver-set north star (BASELINE.json): BERT-base at
>=35% MFU. ``vs_baseline`` therefore reports achieved-MFU / 0.35 so that
1.0 == target met. MFU uses the standard 6N + 12*L*S*d transformer
FLOPs-per-token estimate against the device's peak matmul FLOPs.

The bench runs on a TPU or fails: there is no CPU fallback. Only an
explicit ``JAX_PLATFORMS=cpu`` gives the CPU, for the ``--dryrun`` smokes
of ``tools/run_ci.sh`` (a CPU run reports no MFU). Any exception exits
non-zero. One process: nothing here starts a child that needs the chip.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp


# peak bf16 matmul FLOPs per chip by device kind (public spec sheets)
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5e": 197e12,
    "TPU v5 lite": 197e12,
    "TPU v5p": 459e12,
    "TPU v6e": 918e12,
    "TPU v6 lite": 918e12,
}

def device_peak_flops(dev) -> float:
    """Published peak bf16 matmul FLOP/s of ``dev``. A device kind that
    is not in ``PEAK_FLOPS`` is an error, never a default."""
    kind = getattr(dev, "device_kind", "")
    for name, peak in PEAK_FLOPS.items():
        if kind.lower().startswith(name.lower()):
            return peak
    raise ValueError(f"no published peak FLOP/s for device kind {kind!r} "
                     f"(known: {', '.join(PEAK_FLOPS)})")


def mfu_fields(dev, flops_per_sec: float) -> dict:
    """``{"mfu", "vs_baseline"}``: achieved model FLOP/s against the
    device's published peak (``vs_baseline`` = MFU / the 0.35 north
    star). A CPU smoke run has no peak to stand against: both are None
    there, never a made-up number."""
    if dev.platform != "tpu":
        return {"mfu": None, "vs_baseline": None}
    mfu = flops_per_sec / device_peak_flops(dev)
    return {"mfu": round(mfu, 4), "vs_baseline": round(mfu / 0.35, 4)}


def count_params(tree) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(tree))


def metrics_log_path() -> str:
    """Where this bench run's JSONL telemetry goes (observability.runlog
    schema). Overridable so CI/scrapers can collect it."""
    import os
    return os.environ.get("PADDLE_TPU_METRICS_LOG",
                          "/tmp/paddle_tpu_bench_metrics.jsonl")


def write_bench_telemetry(result: dict) -> str | None:
    """Emit the bench run through the observability subsystem: one JSONL
    step record per timed step (same numbers as the stdout JSON), a
    summary record, registry gauges, and a Prometheus exposition dump
    next to the log. Then schema-validate the log by INVOKING
    tools/check_metrics_log.py — malformed telemetry fails the bench
    (an 'error' field in the JSON line) instead of polluting BENCH_*.

    Returns the log path, or None when the bench produced no telemetry
    (error runs)."""
    import os
    import subprocess

    from paddle_tpu import observability as obs

    tel = result.pop("_telemetry", None)
    if tel is None:
        return None
    path = metrics_log_path()
    try:
        if os.path.exists(path):
            os.remove(path)  # one bench run == one log
    except OSError:
        pass
    steps = max(int(tel["steps"]), 1)
    dt = float(tel["dt"])
    per_step = dt / steps
    ex = float(tel.get("examples_per_step", 0.0))
    tok = tel.get("tokens_per_step")
    with obs.RunLogWriter(path, meta={"bench": result.get("metric")}) as w:
        for i in range(steps):
            rec = {"step": i + 1,
                   "step_time_s": round(per_step, 6),
                   "examples_per_sec": round(ex / per_step, 3),
                   "compiles_cum": obs.compile_count()}
            if tok:
                rec["tokens_per_sec"] = round(tok / per_step, 3)
            w.write(rec)
        w.write({"kind": "summary", "metric": result.get("metric"),
                 "value": result.get("value"),
                 "vs_baseline": result.get("vs_baseline")})
    g = obs.gauge("bench_value", "headline bench metric value")
    g.set(float(result.get("value") or 0.0),
          metric=str(result.get("metric")))
    obs.gauge("bench_vs_baseline").set(
        float(result.get("vs_baseline") or 0.0),
        metric=str(result.get("metric")))
    with open(path + ".prom", "w") as f:
        f.write(obs.render_prometheus())
    check = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "tools", "check_metrics_log.py")
    proc = subprocess.run(
        [sys.executable, check, path, "--require-steps", str(steps)],
        capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench telemetry failed validation: {proc.stderr.strip()}")
    return path


def acquire_device():
    """The device the bench runs on: a TPU, or the run fails. Only an
    explicit ``JAX_PLATFORMS=cpu`` (the ``--dryrun`` smokes of
    ``tools/run_ci.sh``) gives the CPU."""
    dev = jax.devices()[0]      # raises when no backend comes up
    explicit_cpu = os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
    if dev.platform != "tpu" and not explicit_cpu:
        raise RuntimeError(
            f"bench.py needs a TPU, JAX found {dev.platform!r}; set "
            "JAX_PLATFORMS=cpu explicitly for a CPU smoke run")
    return dev


def run_bench_resnet(dev):
    """ResNet-50 training throughput (BASELINE config[1]): images/s/chip
    + MFU. FLOPs per step come from XLA's own cost analysis of the
    compiled train step (conv-appropriate by construction: every conv's
    2*H*W*Cin*Cout*k^2 MACs are counted by the compiler, fwd+bwd+opt)."""
    from paddle_tpu import optimizer as opt
    from paddle_tpu.core import dtypes
    from paddle_tpu.models.resnet import ResNet50
    from paddle_tpu.train import build_train_step, make_train_state

    on_tpu = dev.platform == "tpu"
    batch_size = 128 if on_tpu else 2  # swept: 128 ~= 256 > 64 on v5e
    hw = 224 if on_tpu else 32
    steps = 20 if on_tpu else 2
    num_classes = 1000 if on_tpu else 10

    # s2d: the 7x7/s2 stem re-expressed as a blocked 4x4/s1 conv (same
    # function — models/resnet.py stem_weights_to_s2d); never slower on
    # v5e, +4% at batch 256
    model = ResNet50(num_classes=num_classes,
                     stem="s2d" if on_tpu else "conv7")
    optimizer = opt.Momentum(learning_rate=0.1, momentum=0.9)
    state = make_train_state(model, optimizer, jax.random.PRNGKey(0))

    def loss_fn(params, **batch):
        return model.loss(params, training=True, **batch)

    policy = dtypes.get_policy("bf16") if on_tpu else None
    step = jax.jit(build_train_step(loss_fn, optimizer, policy=policy),
                   donate_argnums=(0,))

    key = jax.random.PRNGKey(1)
    batch = dict(
        image=jax.random.normal(key, (batch_size, hw, hw, 3), jnp.float32),
        label=jax.random.randint(key, (batch_size,), 0, num_classes,
                                 jnp.int32),
    )

    # XLA's flop count for the whole compiled step
    cost = step.lower(state, **batch).compile().cost_analysis()
    flops_per_step = float(cost["flops"])

    # two warmup steps: step 0 compiles; a state-signature change on
    # step 1 (e.g. a dtype drift bug) would otherwise put a silent
    # recompile inside the timed window
    for _ in range(2):
        state, metrics = step(state, **batch)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, **batch)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    final_loss = float(metrics["loss"])

    images_per_sec = batch_size * steps / dt
    return {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(images_per_sec, 2),
        "unit": "images/s/chip",
        **mfu_fields(dev, flops_per_step * steps / dt),
        "device": getattr(dev, "device_kind", dev.platform),
        "batch_size": batch_size,
        "image_size": hw,
        "flops_per_step": flops_per_step,
        "loss": round(final_loss, 4),
        "_telemetry": {"steps": steps, "dt": dt,
                       "examples_per_step": batch_size},
    }


def run_bench(dev):
    from paddle_tpu import optimizer as opt
    from paddle_tpu.core import dtypes
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    from paddle_tpu.train import build_train_step, make_train_state

    on_tpu = dev.platform == "tpu"

    cfg = BertConfig.base(dropout=0.0, attn_dropout=0.0)
    seq = 512
    batch_size = 48 if on_tpu else 2  # swept: 48 > 32 > 8 on v5e
    steps = 20 if on_tpu else 3
    if not on_tpu:  # CPU smoke config: keep the same code path, tiny model
        cfg = BertConfig.tiny(dropout=0.0, attn_dropout=0.0, attn_impl="xla")
        seq = 64

    model = BertForPretraining(cfg)
    optimizer = opt.AdamW(learning_rate=1e-4)
    state = make_train_state(model, optimizer, jax.random.PRNGKey(0))

    def loss_fn(params, **batch):
        # training=True: bench the real training path (dropout=0 here, but
        # keep the graph the one training uses)
        return model.loss(params, training=True, **batch)

    policy = dtypes.get_policy("bf16") if on_tpu else None
    step = jax.jit(build_train_step(loss_fn, optimizer, policy=policy),
                   donate_argnums=(0,))

    key = jax.random.PRNGKey(1)
    batch = dict(
        input_ids=jax.random.randint(key, (batch_size, seq), 0,
                                     cfg.vocab_size, jnp.int32),
        token_type_ids=jnp.zeros((batch_size, seq), jnp.int32),
        attention_mask=jnp.ones((batch_size, seq), bool),
        mlm_labels=jax.random.randint(key, (batch_size, seq), 0,
                                      cfg.vocab_size, jnp.int32),
        mlm_mask=(jax.random.uniform(key, (batch_size, seq)) < 0.15
                  ).astype(jnp.float32),
        nsp_labels=jnp.zeros((batch_size,), jnp.int32),
    )

    state, metrics = step(state, **batch)     # warmup (compile)
    jax.block_until_ready(state)

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, **batch)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    final_loss = float(metrics["loss"])

    tokens_per_step = batch_size * seq
    tokens_per_sec = tokens_per_step * steps / dt

    n_params = count_params(state["params"])
    flops_per_token = 6 * n_params + 12 * cfg.num_layers * seq * cfg.hidden_size

    return {
        "metric": "bert_base_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/s/chip",
        **mfu_fields(dev, tokens_per_sec * flops_per_token),
        "device": getattr(dev, "device_kind", dev.platform),
        "batch_size": batch_size,
        "seq_len": seq,
        "params": n_params,
        "loss": round(final_loss, 4),
        "_telemetry": {"steps": steps, "dt": dt,
                       "examples_per_step": batch_size,
                       "tokens_per_step": tokens_per_step},
    }


def run_bench_transformer(dev):
    """Transformer-big WMT en-de, packed variable-length training
    (BASELINE config[3]): REAL (non-pad) tokens/s/chip through the packed
    path, with the padded one-sequence-per-row layout timed on the same
    compiled shapes as the contrast — ``packed_vs_padded`` is the
    measured win of data/packing.py (same step wall-clock, more real
    tokens per slab). MFU from XLA's cost analysis of the packed step."""
    import numpy as np

    from paddle_tpu import optimizer as opt
    from paddle_tpu.core import dtypes
    from paddle_tpu.data import packing
    from paddle_tpu.models.transformer import Transformer, TransformerConfig
    from paddle_tpu.train import build_train_step, make_train_state

    on_tpu = dev.platform == "tpu"
    if on_tpu:
        cfg = TransformerConfig.big(dropout=0.0, attn_dropout=0.0,
                                    vocab_size=32768, max_len=256)
        src_len = tgt_len = 256
        rows = 16
        steps = 12
        n_pairs = 1500
    else:
        cfg = TransformerConfig.tiny(dropout=0.0, attn_dropout=0.0,
                                     max_len=32, attn_impl="xla")
        src_len = tgt_len = 32
        rows = 2
        steps = 2
        n_pairs = 40

    model = Transformer(cfg)
    optimizer = opt.Adam(learning_rate=1e-4)
    state = make_train_state(model, optimizer, jax.random.PRNGKey(0))

    # WMT-like ragged lengths: lognormal, clipped to the bucket
    rng = np.random.default_rng(0)
    lens = np.clip(rng.lognormal(3.0, 0.6, n_pairs).astype(np.int64),
                   4, src_len - 1)
    srcs = [rng.integers(3, cfg.vocab_size, n).astype(np.int32)
            for n in lens]
    tins = [np.concatenate([[cfg.bos_id], s]).astype(np.int32)[:tgt_len]
            for s in srcs]
    touts = [np.concatenate([s, [cfg.eos_id]]).astype(np.int32)[:tgt_len]
             for s in srcs]

    def loss_fn(params, **b):
        return model.loss_packed(
            params, b["src"], b["src_seg"], b["src_pos"], b["tgt"],
            b["tgt_out"], b["tgt_seg"], b["tgt_pos"], training=True)

    policy = dtypes.get_policy("bf16") if on_tpu else None
    step = jax.jit(build_train_step(loss_fn, optimizer, policy=policy),
                   donate_argnums=(0,))

    def batch_stream(packed: bool):
        if packed:
            it = packing.packed_batches(
                srcs, tins, rows_per_batch=rows, src_len=src_len,
                tgt_len=tgt_len, tgt_extras={"tgt_out": touts})
        else:
            # one sequence per row, same compiled shapes (the LoD-free
            # padded layout the reference trains on)
            def padded():
                for lo in range(0, len(srcs), rows):
                    chunk = list(range(lo, min(lo + rows, len(srcs))))
                    b = {k: np.zeros((rows, src_len if "src" in k
                                      else tgt_len), np.int32)
                         for k in ("src", "src_seg", "src_pos", "tgt",
                                   "tgt_seg", "tgt_pos", "tgt_out")}
                    for ri, i in enumerate(chunk):
                        s, ti, to = srcs[i], tins[i], touts[i]
                        b["src"][ri, :len(s)] = s
                        b["src_seg"][ri, :len(s)] = 1
                        b["src_pos"][ri, :len(s)] = np.arange(len(s))
                        b["tgt"][ri, :len(ti)] = ti
                        b["tgt_seg"][ri, :len(ti)] = 1
                        b["tgt_pos"][ri, :len(ti)] = np.arange(len(ti))
                        b["tgt_out"][ri, :len(to)] = to
                    yield b
            it = padded()
        for b in it:
            yield {k: jnp.asarray(v) for k, v in b.items()}

    def timed(packed: bool, st):
        import itertools
        batches = list(itertools.islice(batch_stream(packed), steps + 1))
        real = sum(int((np.asarray(b["tgt_seg"]) > 0).sum())
                   for b in batches[1:])
        slots = sum(b["tgt_seg"].size for b in batches[1:])
        st, m = step(st, **batches[0])     # warmup/compile
        jax.block_until_ready(st)
        t0 = time.perf_counter()
        for b in batches[1:]:
            st, m = step(st, **b)
        jax.block_until_ready(st)
        dt = time.perf_counter() - t0
        loss = float(m["loss"])
        return real / dt, dt / len(batches[1:]), real / slots, loss, st

    first = next(batch_stream(False))       # shapes only; avoids a
    cost = step.lower(state, **first).compile().cost_analysis()  # full
    flops_per_step = float(cost["flops"])                     # pack pass

    packed_tps, step_s, eff, loss, state = timed(True, state)
    padded_tps, _, _, _, _ = timed(False, state)

    return {
        "metric": "transformer_big_packed_tokens_per_sec_per_chip",
        "value": round(packed_tps, 2),
        "unit": "real tokens/s/chip",
        **mfu_fields(dev, flops_per_step / step_s),
        "packed_vs_padded": round(packed_tps / max(padded_tps, 1e-9), 4),
        "padded_tokens_per_sec": round(padded_tps, 2),
        "packing_efficiency": round(eff, 4),
        "device": getattr(dev, "device_kind", dev.platform),
        "rows_per_batch": rows,
        "src_len": src_len,
        "loss": round(loss, 4),
        "_telemetry": {"steps": steps, "dt": step_s * steps,
                       "examples_per_step": rows,
                       "tokens_per_step": packed_tps * step_s},
    }


def run_bench_deepfm(dev):
    """DeepFM CTR with the host-resident KV embedding engine (BASELINE
    config[4]): examples/s/chip with pull/push PREFETCH overlap on, and
    the same stream with overlap off — ``vs_baseline`` is the measured
    prefetch speedup, the number behind parallel/host_kv.py's "prefetch
    overlaps the device step" design claim.

    Honest-number notes (ISSUE 7 satellite): the original loop issued
    the next batch's dedup (np.unique over B*F ids) BEFORE dispatching
    the device step, putting it on the critical path — prefetch then
    measured ~0.73-0.96x (slower than sync). run_kv_epoch now issues
    the prefetch after step dispatch, which removes the regression; on
    an N-core CPU box with the XLA step already using every core the
    remaining overlap is structurally ~neutral (pull threads timeshare
    with the step — there is no idle resource to hide the pull behind,
    unlike TPU where the device step frees the host), so the CPU
    expectation is ~1.0x and the bench takes best-of-2 per mode to keep
    ambient load spikes from masquerading as regressions."""
    import numpy as np

    from paddle_tpu import optimizer as opt
    from paddle_tpu.models.deepfm import DeepFMHostKV
    from paddle_tpu.parallel.host_kv import (HostKVEmbedding, HostKVStore,
                                             build_kv_train_step,
                                             run_kv_epoch)

    on_tpu = dev.platform == "tpu"
    fields = 26                           # criteo-style sparse fields
    dim = 16 if on_tpu else 8
    # CPU smoke needs non-trivial work per batch too: when the "device"
    # step is near-instant the prefetch thread's sync overhead swamps the
    # overlap and the ratio is meaningless
    batch = 4096 if on_tpu else 2048
    n_batches = 24 if on_tpu else 8
    vocab = 2_000_000 if on_tpu else 500_000

    model = DeepFMHostKV(num_fields=fields, embed_dim=dim,
                         hidden=(400, 400) if on_tpu else (64, 64))
    optimizer = opt.Adam(learning_rate=1e-3)
    params = model.init(jax.random.PRNGKey(0))
    state0 = {"params": params, "opt": optimizer.init(params),
              "step": jnp.zeros((), jnp.int32)}
    step = jax.jit(build_kv_train_step(
        lambda p, rows, inv, label: model.loss(p, rows, inv, label),
        optimizer))

    rng = np.random.default_rng(0)
    all_batches = []
    for _ in range(n_batches):
        # zipf-ish skew: a hot head + a heavy uniform tail, like CTR logs
        hot = rng.integers(0, 1000, size=(batch, fields // 2))
        tail = rng.integers(1000, vocab,
                            size=(batch, fields - fields // 2))
        ids = np.concatenate([hot, tail], 1).astype(np.int64)
        label = (rng.random(batch) < 0.2).astype(np.float32)
        all_batches.append(dict(feat_ids=ids, label=jnp.asarray(label)))

    def timed(prefetch: bool):
        store = HostKVStore(1 + dim, optimizer="adagrad", seed=0)
        emb = HostKVEmbedding(store, lr=0.05, min_bucket=1 << 12)
        state = jax.tree_util.tree_map(jnp.copy, state0)
        # warmup (compile + touch the hot rows once)
        state, _ = run_kv_epoch(step, state, emb, iter(all_batches[:1]),
                                ids_key="feat_ids", prefetch=prefetch)
        t0 = time.perf_counter()
        state, hist = run_kv_epoch(step, state, emb, iter(all_batches),
                                   ids_key="feat_ids", prefetch=prefetch)
        dt = time.perf_counter() - t0
        loss = float(np.mean([float(m["loss"]) for m in hist]))
        return batch * n_batches / dt, loss

    # best-of-2 per mode: a 2-core CI box sees ambient load spikes
    eps_on, loss = max((timed(prefetch=True) for _ in range(2)),
                       key=lambda r: r[0])
    eps_off, _ = max((timed(prefetch=False) for _ in range(2)),
                     key=lambda r: r[0])
    return {
        "metric": "deepfm_examples_per_sec_per_chip",
        "value": round(eps_on, 2),
        "unit": "examples/s/chip",
        # the overlap claim, quantified: >1.0 == prefetch hides KV time
        "vs_baseline": round(eps_on / max(eps_off, 1e-9), 4),
        "prefetch_speedup": round(eps_on / max(eps_off, 1e-9), 4),
        "examples_per_sec_no_prefetch": round(eps_off, 2),
        "prefetch_note": ("cpu: step already saturates every core, so "
                          "overlap is ~neutral by construction; the "
                          "<1.0x regression (dedup on the critical "
                          "path) is fixed in run_kv_epoch"
                          if dev.platform != "tpu" else ""),
        "device": getattr(dev, "device_kind", dev.platform),
        "batch_size": batch,
        "fields": fields,
        "embed_dim": dim,
        "loss": round(loss, 4),
        "_telemetry": {"steps": n_batches,
                       "dt": batch * n_batches / max(eps_on, 1e-9),
                       "examples_per_step": batch},
    }


EMBED_SERVE_SCHEMA = ("metric", "value", "unit", "vs_baseline",
                      "qps_cached", "qps_cold", "speedup_vs_cold",
                      "lookup_p50_s", "lookup_p99_s", "cold_batch_p99_s",
                      "miss_pull_p99_s", "hit_rate", "evictions",
                      "streaming_rows_applied", "staleness_seconds",
                      "recompiles_after_warmup", "capacity", "vocab_size",
                      "batch_size", "fields", "embed_dim", "num_batches",
                      "device")


def embed_serve_json_path(dryrun: bool) -> str:
    import os
    if dryrun:  # CI smoke must not dirty the checkout
        return os.environ.get("PADDLE_TPU_BENCH_EMBED_SERVE",
                              "/tmp/BENCH_EMBED_SERVE.json")
    return os.environ.get(
        "PADDLE_TPU_BENCH_EMBED_SERVE",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_EMBED_SERVE.json"))


def run_bench_embedding_serving(dev, dryrun=False):
    """Online embedding serving (ISSUE 7 acceptance): DeepFM inference
    QPS + p99 lookup latency through the device-cached
    ``EmbeddingServingEngine`` versus the COLD full-table path — every
    batch re-pulls the whole (vocab, dim) table from the host KV store
    and ``device_put``s it before the forward (the no-cache way to
    serve the same freshness guarantee when the table lives beyond
    HBM). Traffic is zipf-ish CTR: a hot head covering most lookups
    (the stated hit-rate regime) plus a uniform cold tail that churns
    the LRU. A trainer thread streams row updates through the
    StreamingUpdateChannel WHILE the cached pass serves — the online-
    learning mix the subsystem exists for — and both paths read the
    same store, so neither side serves stale rows beyond the engine's
    bound. Zero steady-state recompiles is RecompileDetector-ASSERTED
    (any retrace fails the bench), and the hit-rate / staleness gauges
    must come out populated. ``vs_baseline`` is speedup/2.0 — 1.0 ==
    the >=2x acceptance target. Emits BENCH_EMBED_SERVE.json (schema
    self-validated) next to this file (dryrun: /tmp)."""
    import numpy as np

    from paddle_tpu import embedding_serving as es
    from paddle_tpu import observability as obs
    from paddle_tpu.models.deepfm import DeepFMHostKV
    from paddle_tpu.parallel.host_kv import HostKVStore

    on_tpu = dev.platform == "tpu"
    if on_tpu:
        vocab, fields, dim, batch = 2_000_000, 26, 16, 2048
        head, capacity, n_batches, min_bucket = 8192, 1 << 16, 48, 8192
        hidden = (400, 400)
    elif dryrun:
        vocab, fields, dim, batch = 50_000, 8, 8, 256
        head, capacity, n_batches, min_bucket = 512, 2048, 6, 256
        hidden = (32,)
    else:
        vocab, fields, dim, batch = 200_000, 26, 8, 1024
        head, capacity, n_batches, min_bucket = 4096, 1 << 15, 24, 4096
        hidden = (64, 64)

    model = DeepFMHostKV(num_fields=fields, embed_dim=dim, hidden=hidden)
    params = model.init(jax.random.PRNGKey(0))
    store = HostKVStore(1 + dim, optimizer="adagrad", init_scale=0.01,
                        seed=0)
    reg = obs.MetricsRegistry()
    channel = es.StreamingUpdateChannel(store, registry=reg)
    eng = es.EmbeddingServingEngine(
        store, model, params, capacity=capacity, policy="lru",
        min_bucket=min_bucket, max_pending=4, channel=channel,
        max_staleness_s=5.0, registry=reg)

    rng = np.random.default_rng(0)

    def make_batch():
        # 80% of lookups hit the hot head, 20% the uniform cold tail —
        # the zipf-ish CTR mix the stated hit rate comes from
        hot = rng.integers(0, head, size=(batch, fields))
        tail = rng.integers(head, vocab, size=(batch, fields))
        pick = rng.random((batch, fields)) < 0.8
        return np.where(pick, hot, tail).astype(np.int64)

    batches = [make_batch() for _ in range(n_batches)]

    # startup compiles: every cache gather/install bucket + the DeepFM
    # forward per gather width; everything timed below is steady state
    eng.warmup((batch, fields))
    for b in batches[:2]:           # populate the hot head
        eng.serve(b)
    det = obs.RecompileDetector("embed_serve_bench", warmup=0,
                                registry=reg)

    def push_updates(n_rows=64):
        ids = rng.integers(0, head, size=(n_rows,)).astype(np.int64)
        rows = rng.normal(0, 0.01, size=(n_rows, 1 + dim)).astype(
            np.float32)
        channel.push_rows(ids, rows)

    # --- cached pass: pipelined submit/step (miss pulls overlap the
    # previous batch's device work), trainer pushes streaming in.
    # Best-of-2 passes over FRESH same-distribution batches: a 2-core
    # CI box sees ambient load spikes that would otherwise masquerade
    # as engine regressions
    def cached_pass():
        # returns wall time AND this pass's own latency/staleness
        # numbers, so the reported percentiles come from the SAME pass
        # as the reported QPS (best-of-2 exists because ambient CI load
        # can hit one pass — mixing pass-1 QPS with pass-2 latencies
        # would make the artifact internally inconsistent)
        reg.unregister("embedding_serving_lookup_seconds")
        bs = [make_batch() for _ in range(n_batches)]
        t0 = time.perf_counter()
        for i, b in enumerate(bs):
            if i % 4 == 3:
                push_updates()
            eng.submit(b)
            while eng.pending() >= 2:
                eng.step()
        while eng.pending():
            eng.step()
        dt = time.perf_counter() - t0
        lk = reg.histogram("embedding_serving_lookup_seconds")
        return (dt, lk.quantile(0.5), lk.quantile(0.99),
                reg.gauge("embedding_serving_staleness_seconds").value())

    dt_cached, lk_p50, lk_p99, staleness = min(
        (cached_pass() for _ in range(2)), key=lambda r: r[0])
    det.check()
    qps_cached = batch * n_batches / dt_cached
    hit_rate = reg.gauge("embedding_serving_hit_rate").value()

    # --- cold pass: per batch, pull the FULL table from the store,
    # device_put it, and run the same jitted forward with feat_ids
    # indexing the whole table (compile excluded by a warm call)
    all_ids = np.arange(vocab, dtype=np.int64)
    cold_fwd = jax.jit(lambda p, tbl, inv: model.predict_proba(
        p, tbl, inv))
    table_np = store.pull(all_ids)
    np.asarray(cold_fwd(params, jax.device_put(table_np),
                        jnp.asarray(batches[0].astype(np.int32))))

    def cold_pass():
        times = []
        t0 = time.perf_counter()
        for b in batches:
            tb = time.perf_counter()
            tbl = jax.device_put(store.pull(all_ids))
            out = cold_fwd(params, tbl, jnp.asarray(b.astype(np.int32)))
            np.asarray(out)
            times.append(time.perf_counter() - tb)
        return time.perf_counter() - t0, times

    dt_cold, cold_times = min((cold_pass() for _ in range(2)),
                              key=lambda r: r[0])
    qps_cold = batch * n_batches / dt_cold

    channel.flush()
    speedup = qps_cached / max(qps_cold, 1e-9)
    result = {
        "metric": "embedding_serving_examples_per_sec",
        "value": round(qps_cached, 2),
        "unit": "examples/s",
        "vs_baseline": round(speedup / 2.0, 4),  # 1.0 == the 2x target
        "qps_cached": round(qps_cached, 2),
        "qps_cold": round(qps_cold, 2),
        "speedup_vs_cold": round(speedup, 4),
        "lookup_p50_s": round(lk_p50, 6),
        "lookup_p99_s": round(lk_p99, 6),
        "cold_batch_p99_s": round(float(np.percentile(cold_times, 99)),
                                  6),
        "miss_pull_p99_s": round(reg.histogram(
            "embedding_serving_miss_latency_seconds").quantile(0.99), 6),
        "hit_rate": round(hit_rate, 4),
        "evictions": int(reg.counter(
            "embedding_cache_evictions_total").value()),
        "streaming_rows_applied": int(reg.counter(
            "embedding_stream_rows_applied_total").value()),
        "staleness_seconds": round(staleness, 6),
        "recompiles_after_warmup": det.recompiles,
        "capacity": capacity,
        "vocab_size": vocab,
        "batch_size": batch,
        "fields": fields,
        "embed_dim": dim,
        "num_batches": n_batches,
        "device": getattr(dev, "device_kind", dev.platform),
        "dryrun": bool(dryrun),
        "_telemetry": {"steps": n_batches, "dt": dt_cached,
                       "examples_per_step": batch},
    }
    missing = [k for k in EMBED_SERVE_SCHEMA if k not in result]
    if missing:
        raise RuntimeError(f"BENCH_EMBED_SERVE schema self-check "
                           f"failed: missing {missing}")
    if result["recompiles_after_warmup"] != 0:
        raise RuntimeError(
            f"steady-state embedding serving recompiled "
            f"{det.recompiles}x — fixed-shape invariant broken (a "
            "gather/install/forward bucket missed by warmup)")
    if not 0.0 < result["hit_rate"] <= 1.0:
        raise RuntimeError(
            f"hit-rate gauge not populated: {result['hit_rate']}")
    if result["streaming_rows_applied"] <= 0:
        raise RuntimeError("streaming channel applied no rows — the "
                           "online-update half of the bench is dead")
    path = embed_serve_json_path(dryrun)
    with open(path, "w") as f:
        json.dump({k: v for k, v in result.items()
                   if k != "_telemetry"}, f, indent=2)
    result["bench_json"] = path
    return result


ROUTER_SCHEMA = ("metric", "value", "unit", "vs_baseline",
                 "aggregate_tokens_per_sec", "replica_scaling",
                 "scaling_2x", "scaling_4x",
                 "ttft_interactive_p99_s", "ttft_budget_s",
                 "ttft_slo_met", "migrations", "migration_parity_ok",
                 "affinity_routed", "balance_routed",
                 "prefix_tokens_shared",
                 "recompiles_after_warmup", "num_requests",
                 "replica_slots", "decode_cap",
                 "trace_json", "trace_spans", "device", "chaos",
                 "headroom", "postmortem_dir")

# the chaos variant's sub-schema (ISSUE 14) — shared with
# tools/check_metrics_log.py:validate_chaos_section so CI and the bench
# pin the same contract
CHAOS_SCHEMA = ("lost_requests", "redrive_parity", "redrives",
                "redriven_requests", "shed_structured", "ejected",
                "goodput_tokens_per_sec", "goodput_no_chaos",
                "goodput_ratio", "breaker_cycle_ok",
                "breaker_transitions", "recompiles",
                "postmortems", "postmortem_reasons",
                "postmortem_valid", "postmortem_files")


def router_json_path(dryrun: bool) -> str:
    import os
    if dryrun:  # CI smoke must not dirty the checkout
        return os.environ.get("PADDLE_TPU_BENCH_ROUTER",
                              "/tmp/BENCH_ROUTER.json")
    return os.environ.get(
        "PADDLE_TPU_BENCH_ROUTER",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_ROUTER.json"))


def run_bench_router(dev, dryrun=False):
    """Multi-replica serving fleet (ISSUE 11 acceptance): N paged
    ServingEngine replicas behind the prefix-affinity FleetRouter.

    Replicas are stepped round-robin on ONE host here, so wall-clock
    cannot show fleet scaling; instead each replica's BUSY time (wall
    seconds inside its own step calls) is measured and the fleet's
    aggregate tokens/s is ``total tokens / max per-replica busy`` —
    the critical path if every replica had its own accelerator, which
    is exactly what the router controls: a balance miss concentrates
    busy time on one replica and the scaling number drops. Legs:

    - scaling: the same burst (fresh random prompts, same length mix)
      through 1/2/4-replica fleets; ``scaling_2x = agg2/agg1`` with
      the >=1.6x acceptance target;
    - SLO probes: interactive-lane probes trickled in while a burst
      that saturates a single engine runs on the 2-replica fleet —
      probe TTFT p99 vs the stated budget;
    - affinity: shared-system-prompt traffic after one publisher wave;
      the router must place followers where the prefix pages are hot
      (prefix_tokens_shared counts the skipped prefill);
    - migration: the same burst run twice on 2 replicas, once clean and
      once with a mid-decode drain of one replica (live migration of
      queued + in-flight requests) — greedy outputs must be
      byte-identical and the whole bench must stay at ZERO recompiles
      fleet-wide (every replica fully warmed up front, migration page
      IO included).

    Emits BENCH_ROUTER.json (schema self-validated) next to this file
    (dryrun: /tmp) plus a Perfetto trace whose router.route /
    serving.request / router.migrate spans share trace ids across the
    fleet."""
    import numpy as np

    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.serving import fleet
    from paddle_tpu.models.gpt import GPT, GPTConfig

    on_tpu = dev.platform == "tpu"
    if on_tpu:
        cfg = GPTConfig(vocab_size=32768, hidden_size=1024, num_layers=12,
                        num_heads=16, ffn_size=4096, max_position=512,
                        dropout=0.0)
        n_req, slots, page_size, chunk, cap = 48, 8, 16, 64, 64
        len_set = (16, 32, 64, 128, 192)
        attn_impl = "pallas"
        ttft_budget = 1.0
        sysp_len = 4 * page_size + 2
        decode_block = 8
    elif dryrun:
        cfg = GPTConfig.tiny(vocab_size=128, hidden_size=32, num_layers=2,
                             num_heads=2, ffn_size=64, max_position=64,
                             dropout=0.0, attn_impl="xla")
        n_req, slots, page_size, chunk, cap = 8, 2, 4, 8, 8
        len_set = (4, 9, 12)
        attn_impl = "lax"
        ttft_budget = 30.0   # smoke box: schema/plumbing, not latency
        sysp_len = page_size + 2   # fits the tiny per-slot limit
        decode_block = 4     # < cap so a mid-decode drain window exists
    else:
        # CPU measurement config: weight-heavy so batching amortizes
        # weight reads; small enough that 4 replicas' warmups fit a CI
        # box. A single replica (4 slots) is saturated 8x over by the
        # 32-request burst.
        cfg = GPTConfig(vocab_size=1024, hidden_size=256, num_layers=4,
                        num_heads=8, ffn_size=1024, max_position=192,
                        dropout=0.0, attn_impl="xla")
        n_req, slots, page_size, chunk, cap = 32, 4, 16, 32, 32
        len_set = (16, 32, 48, 64)
        attn_impl = "lax"
        ttft_budget = 4.0
        sysp_len = 4 * page_size + 2
        decode_block = 8
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    lens = rng.choice(len_set, n_req)
    hi = max(len_set)
    cache_dtype = jnp.bfloat16 if not on_tpu else None

    reg = obs.MetricsRegistry()
    tracer = obs.Tracer(capacity=65536)

    def make_replica(i):
        eng = serving.ServingEngine(
            model, params, num_slots=slots, page_size=page_size,
            max_tokens_per_slot=hi + cap, prefill_chunk=chunk,
            decode_block=decode_block, attn_impl=attn_impl,
            cache_dtype=cache_dtype, registry=obs.MetricsRegistry(),
            tracer=tracer, ttft_budget_s=ttft_budget)
        return fleet.LocalReplica(eng, name=f"replica{i}")

    # every replica fully warmed (decode + prefill buckets + migration
    # page IO) BEFORE the detector arms: the whole bench below must
    # stay at zero compiles — the fleet-wide fixed-shape invariant
    replicas = [make_replica(i).warmup() for i in range(4)]
    det = obs.RecompileDetector("router_bench", warmup=0, registry=reg)

    def fresh_prompts():
        # same length mix every leg, fresh content (no cross-leg
        # prefix sharing skewing a scaling comparison)
        return [rng.integers(1, cfg.vocab_size, int(n)).astype(np.int32)
                for n in lens]

    leg_tel = {"steps": 0, "dt": 0.0}

    def burst(router, prompts, probes=0, probe_interval=3):
        """Submit everything up front, run to idle; returns (results
        by submission index, probe TTFTs). Records the leg's step
        count + wall time into ``leg_tel`` for the run log."""
        for rep in router.replicas:
            rep.busy_s = 0.0
        frids = [router.submit(p, cap) for p in prompts]
        probe_ids = []
        steps = 0
        t0 = time.perf_counter()
        while not router.idle():
            router.step()
            steps += 1
            if len(probe_ids) < probes and steps % probe_interval == 0:
                pr = rng.integers(1, cfg.vocab_size,
                                  min(len_set)).astype(np.int32)
                probe_ids.append(router.submit(pr, 8,
                                               lane="interactive"))
            if steps > 1_000_000:
                raise RuntimeError("fleet burst did not converge")
        leg_tel["steps"] = steps
        leg_tel["dt"] = time.perf_counter() - t0
        outs = [router.result(f) for f in frids]
        ttfts = [router.request_stats(f)["ttft_s"] for f in probe_ids]
        return outs, ttfts

    # --- scaling legs: 1 / 2 / 4 replicas over the same burst shape
    scaling = {}
    for n in (1, 2, 4):
        router = fleet.FleetRouter(replicas[:n], registry=reg,
                                   tracer=tracer, seed=n)
        outs, _ = burst(router, fresh_prompts())
        assert all(o is not None and len(o) == cap for o in outs), \
            "scaling leg lost requests"
        busy = max(rep.busy_s for rep in replicas[:n])
        scaling[str(n)] = round(n_req * cap / max(busy, 1e-9), 2)
    scaling_2x = scaling["2"] / max(scaling["1"], 1e-9)
    scaling_4x = scaling["4"] / max(scaling["1"], 1e-9)

    # --- SLO probe leg: interactive probes against the 2-replica fleet
    # under the single-engine-saturating burst
    router2 = fleet.FleetRouter(replicas[:2], registry=reg,
                                tracer=tracer, seed=7)
    _, probe_ttfts = burst(router2, fresh_prompts(),
                           probes=max(4, slots),
                           probe_interval=2 if dryrun else 3)
    interactive_p99 = float(np.percentile(probe_ttfts, 99))

    # --- affinity leg: one publisher wave, then shared-prefix traffic;
    # the router must keep followers on the publishing replica
    shared_before = sum(int(r.engine._reg.counter(
        "serving_prefix_shared_tokens_total").value())
        for r in replicas[:2])
    router_a = fleet.FleetRouter(replicas[:2], registry=reg,
                                 tracer=tracer, seed=9)
    sysp = rng.integers(1, cfg.vocab_size, sysp_len).astype(np.int32)
    def shared_prompt():
        return np.concatenate([sysp, rng.integers(
            1, cfg.vocab_size, int(min(len_set))).astype(np.int32)])
    router_a.submit(shared_prompt(), 8)
    router_a.run_until_idle(max_steps=1_000_000)
    for _ in range(n_req // 2):
        router_a.submit(shared_prompt(), 8)
    router_a.run_until_idle(max_steps=1_000_000)
    shared_after = sum(int(r.engine._reg.counter(
        "serving_prefix_shared_tokens_total").value())
        for r in replicas[:2])
    prefix_tokens_shared = shared_after - shared_before
    affinity_routed = router_a.routed_affinity_total

    # --- migration leg: same traffic twice on 2 replicas; the second
    # run drains replica1 mid-decode (queued requests re-routed,
    # in-flight slots live-migrated) — byte-identical greedy outputs
    # required. Sized to ONE replica's slots so the survivor has free
    # capacity to restore into (a drain into a saturated peer rightly
    # aborts — that is the no-request-lost contract, not the bench).
    mig_prompts = fresh_prompts()[:slots]
    router_m = fleet.FleetRouter(replicas[:2], registry=reg,
                                 tracer=tracer, seed=13)
    ref_outs, _ = burst(router_m, mig_prompts)
    router_m2 = fleet.FleetRouter(replicas[:2], registry=reg,
                                  tracer=tracer, seed=13)
    for rep in replicas[:2]:
        rep.busy_s = 0.0
    frids = [router_m2.submit(p, cap) for p in mig_prompts]
    # step until replica1 holds a MID-decode request (some tokens out,
    # more to go) so the drain exercises a genuine in-flight migration
    eng1 = replicas[1].engine
    for _ in range(1_000):
        router_m2.step()
        mid = [i for i in eng1.scheduler.decode_slots()
               if 0 < len(eng1.scheduler.slots[i].generated) < cap]
        if mid:
            break
    else:
        raise RuntimeError("no mid-decode drain window found")
    migrations = router_m2.drain_replica(replicas[1], remove=False)
    while not router_m2.idle():
        router_m2.step()
    replicas[1].draining = False        # hand the replica back
    mig_outs = [router_m2.result(f) for f in frids]
    parity_ok = all(
        m is not None and r is not None and np.array_equal(r, m)
        for r, m in zip(ref_outs, mig_outs))

    # --- chaos leg (ISSUE 14): involuntary failure on the 4-replica
    # fleet — one replica CRASHES mid-burst (ejected, requests
    # redriven exactly-once), another's transport flakes (circuit
    # breaker opens, half-open probes, closes). Gates: 0 requests
    # silently lost, redriven greedy outputs byte-identical to the
    # failure-free run, the breaker completes a visible full cycle,
    # and the whole leg stays at zero recompiles with detection +
    # breakers armed.
    chaos_prompts = fresh_prompts()
    router_cr = fleet.FleetRouter(replicas, registry=reg,
                                  tracer=tracer, seed=17)
    ref_chaos, _ = burst(router_cr, chaos_prompts)
    chaos_clean_busy = max(rep.busy_s for rep in replicas)
    chaos_clean_tokens = sum(len(o) for o in ref_chaos)
    goodput_clean = chaos_clean_tokens / max(chaos_clean_busy, 1e-9)

    crash_step = 4 if dryrun else 6
    c_crash = fleet.ChaosReplica(replicas[1], crash_on_step=crash_step)
    c_flaky = fleet.ChaosReplica(replicas[2], submit_failures=2)
    # breaker trips at 2 failures, well under the death threshold: the
    # flaky replica must CYCLE (open -> half-open -> closed), not eject
    fpol = fleet.FaultPolicy(max_consecutive_failures=6,
                             probe_timeout_s=120.0,
                             breaker_threshold=2,
                             breaker_cooldown_s=0.2, max_redrives=4)
    # flight recorder (ISSUE 16): the crash ejection must ship a
    # schema-validated postmortem bundle next to BENCH_ROUTER.json
    import os
    import shutil
    jpath = router_json_path(dryrun)
    pm_dir = (jpath[:-5] if jpath.endswith(".json") else jpath) \
        + ".postmortems"
    shutil.rmtree(pm_dir, ignore_errors=True)   # this run's bundles only
    router_x = fleet.FleetRouter(
        [replicas[0], c_crash, c_flaky, replicas[3]],
        registry=reg, tracer=tracer, seed=17, faults=fpol,
        postmortem_dir=pm_dir)
    for rep in replicas:
        rep.busy_s = 0.0

    def tiny_prompt():
        return rng.integers(1, cfg.vocab_size,
                            min(len_set)).astype(np.int32)

    # deterministically trip the flaky transport before the burst: keep
    # feeding tiny requests until its breaker opens (p2c favors the
    # always-empty flaky replica, so this converges in a few submits;
    # the failed submits retry on peers — the caller never loses one)
    pre_frids = []
    for _ in range(64):
        pre_frids.append(router_x.submit(tiny_prompt(), 4))
        if (c_flaky.name, "closed", "open") in router_x.breaker_transitions:
            break
    else:
        raise RuntimeError("chaos leg: flaky breaker never opened")
    frids_x = [router_x.submit(p, cap) for p in chaos_prompts]
    steps = 0
    while not router_x.idle():
        router_x.step()
        steps += 1
        if steps > 1_000_000:
            raise RuntimeError("chaos burst did not converge")
    # recovery wave: let the breaker cooldown elapse (a dryrun burst
    # can finish inside it), then the router routes the next submit as
    # the deliberate half-open probe; the healed transport answers and
    # the breaker closes
    time.sleep(fpol.breaker_cooldown_s + 0.05)
    probe_frids = [router_x.submit(tiny_prompt(), 4) for _ in range(2)]
    while not router_x.idle():
        router_x.step()
    chaos_busy = max(rep.busy_s for rep in replicas)
    chaos_outs, chaos_shed, chaos_lost = [], 0, 0
    for f in frids_x:
        o = router_x.result(f)
        chaos_outs.append(o)
        if o is None:
            if router_x.reject_reason(f) is not None:
                chaos_shed += 1
            else:
                chaos_lost += 1
    for f in pre_frids + probe_frids:       # no-silent-loss covers ALL
        if router_x.result(f) is None \
                and router_x.reject_reason(f) is None:
            chaos_lost += 1
    chaos_parity = all(
        o is not None and np.array_equal(r, o)
        for r, o in zip(ref_chaos, chaos_outs))
    chaos_tokens = sum(len(o) for o in chaos_outs if o is not None)
    goodput_chaos = chaos_tokens / max(chaos_busy, 1e-9)
    flaky_trans = [(old, new) for (nm, old, new)
                   in router_x.breaker_transitions
                   if nm == c_flaky.name]
    cycle = [("closed", "open"), ("open", "half_open"),
             ("half_open", "closed")]
    it = iter(flaky_trans)
    breaker_cycle_ok = all(t in it for t in cycle)   # ordered subseq
    # postmortem artifact gate: every ejection (and the flaky breaker
    # opening) pulled a black box; each bundle must validate and the
    # eject bundle's trace ids must join the redrive spans' timeline
    bundles = router_x.postmortems()
    redrive_tids = {s.trace_id for s in tracer.spans()
                    if s.name == "router.redrive" and s.trace_id}
    eject_bundles = [b for b in bundles if b["reason"] == "eject"]
    if not eject_bundles:
        raise RuntimeError("chaos leg: crash ejection shipped no "
                           "postmortem bundle")
    for b in bundles:
        obs.validate_postmortem_bundle(b)
    if not set(eject_bundles[0]["trace_ids"]) & redrive_tids:
        raise RuntimeError(
            "chaos leg: eject postmortem trace ids "
            f"{eject_bundles[0]['trace_ids']} join no router.redrive "
            "span — the bundle cannot be linked to its victims")
    pm_files = sorted(os.listdir(pm_dir)) if os.path.isdir(pm_dir) else []
    if not pm_files:
        raise RuntimeError(f"chaos leg: no postmortem dumped to {pm_dir}")
    for fn in pm_files:
        obs.validate_postmortem_file(os.path.join(pm_dir, fn))
    chaos = {
        "lost_requests": int(chaos_lost),
        "redrive_parity": bool(chaos_parity),
        "redrives": int(router_x.redrives_total),
        # distinct requests redriven (an unlucky request can redrive
        # more than once): unique trace ids on the redrive spans
        "redriven_requests": len({s.trace_id for s in tracer.spans()
                                  if s.name == "router.redrive"}),
        "shed_structured": int(chaos_shed),
        "ejected": int(router_x.ejected_total),
        "goodput_tokens_per_sec": round(goodput_chaos, 2),
        "goodput_no_chaos": round(goodput_clean, 2),
        "goodput_ratio": round(goodput_chaos
                               / max(goodput_clean, 1e-9), 4),
        "breaker_cycle_ok": bool(breaker_cycle_ok),
        "breaker_transitions": [f"{nm}:{old}->{new}" for (nm, old, new)
                                in router_x.breaker_transitions],
        "recompiles": 0,        # re-pinned below after det.check()
        "postmortems": len(bundles),
        "postmortem_reasons": sorted({b["reason"] for b in bundles}),
        "postmortem_valid": True,           # validated above, or raised
        "postmortem_files": pm_files,
    }

    det.check()
    chaos["recompiles"] = det.recompiles

    # --- headroom plane (ISSUE 16): the fleet monitor aggregates the
    # surviving replicas' resource headroom (min across replicas = the
    # fleet bottleneck) — pinned in the committed JSON so a regression
    # in the gauge plumbing fails the bench, not a dashboard
    monitor = fleet.FleetMonitor(router_x, registry=reg)
    mon_h = monitor.collect()
    headroom = mon_h["headroom"]
    if set(headroom) != {"flops", "pages", "slots", "hbm", "spill"}:
        raise RuntimeError(f"fleet headroom plane incomplete: {headroom}")
    if any(not (0.0 <= float(v) <= 1.0) for v in headroom.values()):
        raise RuntimeError(f"fleet headroom out of range: {headroom}")

    # --- trace artifact: the cross-replica timeline (ISSUE acceptance:
    # one trace shows a request crossing the fleet through a migration)
    spans = tracer.spans()
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    for needed in ("router.route", "serving.request", "router.migrate"):
        if needed not in by_name:
            raise RuntimeError(f"trace self-check: no {needed!r} spans")
    req_tids = {s.trace_id for s in by_name["serving.request"]}
    crossing = [s for s in by_name["router.migrate"]
                if s.trace_id in req_tids]
    if not crossing:
        raise RuntimeError("trace self-check: no migration trace joins "
                           "router.migrate to its request spans")
    chrome = tracer.to_chrome()
    obs.chrome_trace_valid(chrome, require_events=len(crossing))
    trace_path = (jpath[:-5] if jpath.endswith(".json") else jpath) \
        + ".trace.json"
    with open(trace_path, "w") as f:
        json.dump(chrome, f)

    result = {
        "metric": "router_aggregate_tokens_per_sec",
        "value": scaling["2"],
        "unit": "tokens/s",
        # 1.0 == the >=1.6x two-replica scaling target
        "vs_baseline": round(scaling_2x / 1.6, 4),
        "aggregate_tokens_per_sec": scaling["2"],
        "replica_scaling": scaling,
        "scaling_2x": round(scaling_2x, 4),
        "scaling_4x": round(scaling_4x, 4),
        "ttft_interactive_p99_s": round(interactive_p99, 6),
        "ttft_budget_s": ttft_budget,
        "ttft_slo_met": bool(interactive_p99 <= ttft_budget),
        "migrations": int(migrations),
        "migration_parity_ok": bool(parity_ok),
        "affinity_routed": int(affinity_routed),
        "balance_routed": int(router_a.routed_balance_total),
        "prefix_tokens_shared": int(prefix_tokens_shared),
        "recompiles_after_warmup": det.recompiles,
        "chaos": chaos,
        "headroom": headroom,
        "postmortem_dir": os.path.basename(pm_dir),
        "num_requests": n_req,
        "replica_slots": slots,
        "decode_cap": cap,
        "trace_json": trace_path,
        "trace_spans": len(spans),
        "device": getattr(dev, "device_kind", dev.platform),
        "dryrun": bool(dryrun),
        "_telemetry": {"steps": leg_tel["steps"], "dt": leg_tel["dt"],
                       "examples_per_step": slots,
                       "tokens_per_step": n_req * cap
                       / max(leg_tel["steps"], 1)},
    }
    missing = [k for k in ROUTER_SCHEMA if k not in result]
    if missing:
        raise RuntimeError(f"BENCH_ROUTER schema self-check failed: "
                           f"missing {missing}")
    missing_chaos = [k for k in CHAOS_SCHEMA if k not in chaos]
    if missing_chaos:
        raise RuntimeError(f"BENCH_ROUTER chaos section self-check "
                           f"failed: missing {missing_chaos}")
    if chaos["lost_requests"] != 0:
        raise RuntimeError(
            f"chaos leg lost {chaos['lost_requests']} requests "
            "silently — the no-silent-loss contract broke")
    if not chaos["redrive_parity"]:
        raise RuntimeError("chaos redrive parity broken: redriven "
                           "outputs differ from the failure-free run")
    if chaos["ejected"] < 1 or chaos["redrives"] < 1:
        raise RuntimeError("chaos leg ejected/redrove nothing — the "
                           "crash injection is dead")
    if not chaos["breaker_cycle_ok"]:
        raise RuntimeError(
            f"breaker never completed open->half_open->closed "
            f"(saw {chaos['breaker_transitions']})")
    if not parity_ok:
        raise RuntimeError("migration parity broken: drained run's "
                           "greedy outputs differ from the clean run")
    if migrations < 1:
        raise RuntimeError("drain migrated nothing — the migration leg "
                           "is dead")
    if result["recompiles_after_warmup"] != 0:
        raise RuntimeError(
            f"fleet recompiled {det.recompiles}x after warmup — the "
            "fleet-wide fixed-shape invariant broke (scaling numbers "
            "untrustworthy)")
    import os
    committed = {k: v for k, v in result.items() if k != "_telemetry"}
    committed["trace_json"] = os.path.basename(trace_path)
    with open(jpath, "w") as f:
        json.dump(committed, f, indent=2)
    result["bench_json"] = jpath
    return result


NET_SCHEMA = ("metric", "value", "unit", "vs_baseline",
              "net_tokens_per_sec", "local_tokens_per_sec",
              "transport_overhead_ms_per_token", "transport_parity_ok",
              "wire_codec", "rpc_calls_total",
              "stream_requests", "stream_partials_min",
              "stream_ttft_p99_s", "ttft_budget_s", "ttft_slo_met",
              "netlog", "netlog_valid", "steady_state_recompiles",
              "chaos", "num_requests", "replica_slots", "decode_cap",
              "device", "dryrun")

# socket-chaos sub-schema (ISSUE 17): the PR 12 chaos battery run over
# REAL processes and a real dead socket
NET_CHAOS_SCHEMA = ("lost_requests", "redrive_parity", "redrives",
                    "ejected", "shed_structured", "breaker_cycle_ok",
                    "breaker_transitions", "postmortems",
                    "postmortem_reasons", "postmortem_valid")


def net_json_path(dryrun: bool) -> str:
    import os
    if dryrun:  # CI smoke must not dirty the checkout
        return os.environ.get("PADDLE_TPU_BENCH_NET",
                              "/tmp/BENCH_NET.json")
    return os.environ.get(
        "PADDLE_TPU_BENCH_NET",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_NET.json"))


def run_bench_net_router(dev, dryrun=False):
    """Network serving (ISSUE 17 acceptance): the fleet split across
    REAL processes behind the wire protocol, against the in-process
    LocalReplica fleet as baseline. Legs:

    - transport: the same burst through a 2-process NetReplica fleet
      and a 2-replica in-process fleet — bit-identical greedy outputs
      (the ReplicaHandle contract across a socket) and the transport
      overhead per generated token (RPC framing + checksums + syscalls);
      each replica process must hold ZERO steady-state recompiles
      across the burst (warmup happens server-side before the replica
      announces itself).
    - streaming: a FrontDoor over the net fleet; clients must observe
      >=2 partial token deliveries per request (incremental streaming,
      not buffer-then-flush), streamed TTFT p99 vs the stated budget,
      and the front door's crash-safe netlog must validate (every
      accepted rid terminated exactly once).
    - socket chaos: the PR 12 battery over real sockets — one replica
      process SIGSTOPped until its breaker opens, SIGCONT + cooldown
      and the deliberate half-open probe close it (full
      open→half_open→closed cycle); another replica process is
      ``kill -9``'ed mid-burst — ejected on consecutive transport
      failures, its in-flight requests redriven exactly-once with
      bit-identical outputs, 0 requests lost, and the eject postmortem
      dumped from the CLIENT-side flight recorder (the process that
      could have testified is gone).

    Emits BENCH_NET.json (schema self-validated) next to this file
    (dryrun: /tmp) plus the netlog JSONL the CI validator replays."""
    import os
    import signal

    import numpy as np

    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.serving import fleet
    from paddle_tpu.serving.fleet import net
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.resilience.retry import RetryPolicy

    if dryrun:
        config = dict(vocab_size=128, hidden_size=32, num_layers=2,
                      num_heads=2, ffn_size=64, max_position=64,
                      dropout=0.0, attn_impl="xla")
        n_req, slots, page_size, chunk, cap = 8, 2, 4, 8, 8
        len_set = (4, 9, 12)
        ttft_budget = 30.0   # smoke box: schema/plumbing, not latency
        decode_block = 4
    else:
        # CPU measurement config: sized so THREE subprocess warmups fit
        # a CI box; a single replica is saturated by the burst
        config = dict(vocab_size=1024, hidden_size=256, num_layers=4,
                      num_heads=8, ffn_size=1024, max_position=192,
                      dropout=0.0, attn_impl="xla")
        n_req, slots, page_size, chunk, cap = 12, 4, 16, 32, 24
        len_set = (16, 32, 48)
        ttft_budget = 15.0
        decode_block = 8
    hi = max(len_set)
    cap_stream = 2 * cap          # long decode: >=2 partial deliveries
    engine_kwargs = dict(num_slots=slots, page_size=page_size,
                         max_tokens_per_slot=hi + cap_stream,
                         prefill_chunk=chunk, decode_block=decode_block,
                         attn_impl="lax", ttft_budget_s=ttft_budget)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, config["vocab_size"],
                            int(n)).astype(np.int32)
               for n in rng.choice(len_set, n_req)]

    reg = obs.MetricsRegistry()
    tracer = obs.Tracer(capacity=65536)
    leg_tel = {"steps": 0, "dt": 0.0}

    def burst(router):
        frids = [router.submit(p, cap) for p in prompts]
        steps = 0
        t0 = time.perf_counter()
        while not router.idle():
            router.step()
            steps += 1
            if steps > 1_000_000:
                raise RuntimeError("net burst did not converge")
        dt = time.perf_counter() - t0
        leg_tel["steps"], leg_tel["dt"] = steps, dt
        outs = [router.result(f) for f in frids]
        if any(o is None for o in outs):
            raise RuntimeError("net burst lost requests")
        return outs, dt

    # --- local baseline: the SAME weights/config, in-process ----------
    cfg = GPTConfig.tiny(**config)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def local_replica(i):
        eng = serving.ServingEngine(model, params,
                                    registry=obs.MetricsRegistry(),
                                    tracer=tracer, **engine_kwargs)
        return fleet.LocalReplica(eng, name=f"local{i}").warmup()

    router_local = fleet.FleetRouter([local_replica(i) for i in (0, 1)],
                                     registry=reg, tracer=tracer, seed=3)
    ref_outs, local_dt = burst(router_local)
    total_tokens = sum(len(o) for o in ref_outs)
    local_tps = total_tokens / max(local_dt, 1e-9)

    # --- spawn the replica processes (in parallel: warmup dominates) --
    from concurrent.futures import ThreadPoolExecutor
    names = ("netA", "netB", "netC")
    with ThreadPoolExecutor(len(names)) as ex:
        spawned = list(ex.map(
            lambda nm: net.spawn_replica_server(
                config=config, engine=engine_kwargs, seed=0, name=nm),
            names))
    procs = {nm: proc for nm, (proc, _a) in zip(names, spawned)}
    addrs = {nm: addr for nm, (_p, addr) in zip(names, spawned)}
    try:
        # --- transport leg: 2-process fleet, bit-identical outputs ----
        reps_net = [net.NetReplica(addrs[nm], name=nm, registry=reg)
                    for nm in ("netA", "netB")]
        router_net = fleet.FleetRouter(reps_net, registry=reg,
                                       tracer=tracer, seed=3)
        rc0 = [int(r.health().get("recompiles", 0)) for r in reps_net]
        net_outs, net_dt = burst(router_net)
        rc1 = [int(r.health().get("recompiles", 0)) for r in reps_net]
        steady_recompiles = sum(b - a for a, b in zip(rc0, rc1))
        parity_ok = all(np.array_equal(r, o)
                        for r, o in zip(ref_outs, net_outs))
        net_tps = total_tokens / max(net_dt, 1e-9)
        overhead_ms = (net_dt - local_dt) / max(total_tokens, 1) * 1e3
        rpc_calls = sum(r.calls_total for r in reps_net)

        # --- streaming leg: FrontDoor over the net fleet --------------
        jpath = net_json_path(dryrun)
        netlog = (jpath[:-5] if jpath.endswith(".json") else jpath) \
            + ".netlog.jsonl"
        if os.path.exists(netlog):
            os.remove(netlog)       # this run's ledger only
        door = net.FrontDoor(router_net, netlog_path=netlog,
                             registry=reg).start()
        stream_n = 4
        partials, ttfts = [], []
        try:
            for i in range(stream_n):
                cli = net.FrontDoorClient(door.address)
                try:
                    r = cli.generate(prompts[i % len(prompts)],
                                     cap_stream, tag=f"s{i}",
                                     timeout_s=600.0)
                finally:
                    cli.close()
                if r["tokens"] is None:
                    raise RuntimeError(
                        f"stream request {i} rejected: {r['reject']}")
                if r["streamed"] != r["tokens"][:len(r["streamed"])]:
                    raise RuntimeError(
                        "streamed tokens diverge from the final result")
                partials.append(r["partials"])
                ttfts.append(r["ttft_s"])
        finally:
            door.close()            # terminal-logs anything live
        stream_p99 = float(np.percentile(ttfts, 99))
        netlog_summary = net.validate_netlog_file(
            netlog, require_requests=stream_n)

        # --- socket chaos: breaker cycle (SIGSTOP) + kill -9 ----------
        fast_retry = RetryPolicy(max_attempts=2, base_delay_s=0.05,
                                 max_delay_s=0.2, deadline_s=2.0,
                                 retry_on=(OSError, TimeoutError))
        chaos_reps = {nm: net.NetReplica(
            addrs[nm], name=nm, call_timeout_s=0.75, retry=fast_retry,
            registry=reg) for nm in names}
        fpol = fleet.FaultPolicy(max_consecutive_failures=8,
                                 probe_timeout_s=120.0,
                                 breaker_threshold=2,
                                 breaker_cooldown_s=0.3, max_redrives=4)
        router_x = fleet.FleetRouter(list(chaos_reps.values()),
                                     registry=reg, tracer=tracer,
                                     seed=17, faults=fpol)

        def transitions_of(nm):
            return [(old, new) for (n, old, new)
                    in router_x.breaker_transitions if n == nm]

        # phase 1: stop netC's process; router probes time out (a hung
        # host IS a transport failure), breaker opens well under the
        # death threshold; resume + cooldown + the deliberate half-open
        # probe close it again — the full cycle over a real socket
        os.kill(procs["netC"].pid, signal.SIGSTOP)
        for _ in range(6):
            router_x.step()
            if ("closed", "open") in transitions_of("netC"):
                break
        else:
            raise RuntimeError("chaos: netC breaker never opened")
        os.kill(procs["netC"].pid, signal.SIGCONT)
        time.sleep(fpol.breaker_cooldown_s + 0.05)
        probe_frids = [router_x.submit(rng.integers(
            1, config["vocab_size"], min(len_set)).astype(np.int32), 4)
            for _ in range(3)]
        router_x.run_until_idle(max_steps=1_000_000)
        cycle = [("closed", "open"), ("open", "half_open"),
                 ("half_open", "closed")]
        it = iter(transitions_of("netC"))
        breaker_cycle_ok = all(t in it for t in cycle)  # ordered subseq

        # phase 2: kill -9 netB mid-burst — ejected on consecutive
        # transport failures, requests redriven, outputs bit-identical
        frids_x = [router_x.submit(p, cap) for p in prompts]
        victim_live = [frid for frid, (rep, _l)
                       in router_x._where.items()
                       if rep is chaos_reps["netB"]]
        for _ in range(200):        # let netB emit some tokens first
            router_x.step()
            if any(router_x.progress(f) for f in victim_live):
                break
        procs["netB"].kill()        # SIGKILL: the real dead socket
        procs["netB"].wait()
        steps = 0
        while not router_x.idle():
            router_x.step()
            steps += 1
            if steps > 1_000_000:
                raise RuntimeError("chaos burst did not converge")
        chaos_outs, chaos_shed, chaos_lost = [], 0, 0
        for f in frids_x:
            o = router_x.result(f)
            chaos_outs.append(o)
            if o is None:
                if router_x.reject_reason(f) is not None:
                    chaos_shed += 1
                else:
                    chaos_lost += 1
        for f in probe_frids:       # no-silent-loss covers ALL
            if router_x.result(f) is None \
                    and router_x.reject_reason(f) is None:
                chaos_lost += 1
        chaos_parity = all(
            o is not None and np.array_equal(r, o)
            for r, o in zip(net_outs, chaos_outs))
        bundles = router_x.postmortems()
        for b in bundles:
            obs.validate_postmortem_bundle(b)
        pm_reasons = sorted({b["reason"] for b in bundles})
        if "eject" not in pm_reasons:
            raise RuntimeError("chaos: kill -9 shipped no eject "
                               f"postmortem (saw {pm_reasons})")
        chaos = {
            "lost_requests": int(chaos_lost),
            "redrive_parity": bool(chaos_parity),
            "redrives": int(router_x.redrives_total),
            "ejected": int(router_x.ejected_total),
            "shed_structured": int(chaos_shed),
            "breaker_cycle_ok": bool(breaker_cycle_ok),
            "breaker_transitions": [
                f"{nm}:{old}->{new}" for (nm, old, new)
                in router_x.breaker_transitions],
            "postmortems": len(bundles),
            "postmortem_reasons": pm_reasons,
            "postmortem_valid": True,       # validated above, or raised
        }
        for r in list(chaos_reps.values()) + reps_net:
            r.close()
    finally:
        for proc in procs.values():
            if proc.poll() is None:
                try:
                    os.kill(proc.pid, signal.SIGCONT)  # if still stopped
                except OSError:
                    pass
                proc.kill()
                proc.wait()

    result = {
        "metric": "net_router_tokens_per_sec",
        "value": round(net_tps, 2),
        "unit": "tokens/s",
        # 1.0 == transport costs nothing vs in-process; the gates that
        # actually bind are parity / chaos / streaming, asserted below
        "vs_baseline": round(net_tps / max(local_tps, 1e-9), 4),
        "net_tokens_per_sec": round(net_tps, 2),
        "local_tokens_per_sec": round(local_tps, 2),
        "transport_overhead_ms_per_token": round(overhead_ms, 4),
        "transport_parity_ok": bool(parity_ok),
        "wire_codec": net.default_codec(),
        "rpc_calls_total": int(rpc_calls),
        "stream_requests": stream_n,
        "stream_partials_min": int(min(partials)),
        "stream_ttft_p99_s": round(stream_p99, 6),
        "ttft_budget_s": ttft_budget,
        "ttft_slo_met": bool(stream_p99 <= ttft_budget),
        "netlog": os.path.basename(netlog),
        "netlog_valid": netlog_summary,
        "steady_state_recompiles": int(steady_recompiles),
        "chaos": chaos,
        "num_requests": n_req,
        "replica_slots": slots,
        "decode_cap": cap,
        "device": getattr(dev, "device_kind", dev.platform),
        "dryrun": bool(dryrun),
        "_telemetry": {"steps": leg_tel["steps"], "dt": leg_tel["dt"],
                       "examples_per_step": slots,
                       "tokens_per_step": total_tokens
                       / max(leg_tel["steps"], 1)},
    }
    missing = [k for k in NET_SCHEMA if k not in result]
    if missing:
        raise RuntimeError(f"BENCH_NET schema self-check failed: "
                           f"missing {missing}")
    missing_chaos = [k for k in NET_CHAOS_SCHEMA if k not in chaos]
    if missing_chaos:
        raise RuntimeError(f"BENCH_NET chaos section self-check "
                           f"failed: missing {missing_chaos}")
    if not parity_ok:
        raise RuntimeError("transport parity broken: the net fleet's "
                           "greedy outputs differ from in-process")
    if steady_recompiles != 0:
        raise RuntimeError(
            f"replica processes recompiled {steady_recompiles}x in "
            "steady state — server-side warmup is not covering the "
            "serving shapes")
    if min(partials) < 2:
        raise RuntimeError(
            f"streaming leg delivered min {min(partials)} partial "
            "frames — the front door is buffering, not streaming")
    if chaos["lost_requests"] != 0:
        raise RuntimeError(
            f"socket chaos lost {chaos['lost_requests']} requests "
            "silently — the no-silent-loss contract broke")
    if not chaos["redrive_parity"]:
        raise RuntimeError("socket-chaos redrive parity broken: "
                           "redriven outputs differ")
    if chaos["ejected"] < 1 or chaos["redrives"] < 1:
        raise RuntimeError("socket chaos ejected/redrove nothing — "
                           "the kill -9 injection is dead")
    if not chaos["breaker_cycle_ok"]:
        raise RuntimeError(
            f"breaker never completed open->half_open->closed over "
            f"the socket (saw {chaos['breaker_transitions']})")
    committed = {k: v for k, v in result.items() if k != "_telemetry"}
    with open(jpath, "w") as f:
        json.dump(committed, f, indent=2)
    result["bench_json"] = jpath
    return result


SERVING_SCHEMA = ("metric", "value", "unit", "vs_baseline",
                  "decode_tokens_per_sec", "baseline_tokens_per_sec",
                  "speedup_vs_dense_loop", "end_to_end_tokens_per_sec",
                  "end_to_end_speedup", "decode_seconds_engine",
                  "decode_seconds_dense", "prefill_seconds_engine",
                  "prefill_seconds_dense", "ttft_mean_s", "ttft_max_s",
                  "ttft_p50_s", "ttft_p90_s", "ttft_p99_s",
                  "ttft_interactive_p99_s",
                  "ttft_budget_s", "ttft_slo_met",
                  "queue_wait_p50_s", "queue_wait_p90_s",
                  "queue_wait_p99_s", "admit_to_first_token_p99_s",
                  "slo_burn_rate", "slo_alerts_total",
                  "trace_json", "trace_spans",
                  "prefix_variant",
                  "tokens_per_hbm_byte", "tokens_per_hbm_byte_bf16",
                  "quant_static_bytes_ratio", "quant_speedup",
                  "quant_variant", "spec_accept_rate", "spec_variant",
                  "mean_slot_occupancy", "page_utilization_peak",
                  "decode_recompiles_after_warmup", "num_requests",
                  "num_slots", "page_size", "device")


def serving_json_path(dryrun: bool) -> str:
    import os
    if dryrun:  # CI smoke must not dirty the checkout
        return os.environ.get("PADDLE_TPU_BENCH_SERVING",
                              "/tmp/BENCH_SERVING.json")
    return os.environ.get(
        "PADDLE_TPU_BENCH_SERVING",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_SERVING.json"))


def run_bench_serving(dev, dryrun=False):
    """Continuous-batching serving throughput (ISSUE 4 acceptance): the
    paged ServingEngine versus looping ``GPT.generate(use_cache=True)``
    over the SAME requests — mixed prompt lengths, shared decode cap,
    early-EOS mix. The engine evicts a sequence the step EOS lands and
    backfills the slot; ``generate``'s fixed-trip device loop cannot
    stop early (the lock-step waste the ISSUE motivates paging with),
    so the dense loop burns the full cap on every request. Throughput
    counts USEFUL tokens (up to EOS — both sides emit identical greedy
    streams, so useful counts are identical by construction). Random
    init has no trained stop behavior, so per-request EOS ids are
    derived from reference rollouts (first occurrence of a real emitted
    token near a target stop position); ~1/6 of requests get no EOS and
    run to cap — the long tail. Both sides are warmed (compiles
    excluded). ``vs_baseline`` is speedup/2.0 — 1.0 == the >=2x target.
    ISSUE 6 additions: TTFT/queue-wait p50/p90/p99 percentiles against a
    stated ``ttft_budget_s`` (the machine-checkable SLO), split queue/
    prefill latency accounting, and a shared-prefix variant proving
    prefix/page sharing (prefill tokens computed < prompt tokens
    submitted). Emits BENCH_SERVING.json (schema self-validated, hard-
    fails on any steady-state recompile in either variant) next to this
    file (dryrun: /tmp)."""
    import numpy as np

    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.models.gpt import GPT, GPTConfig

    on_tpu = dev.platform == "tpu"
    if on_tpu:
        cfg = GPTConfig(vocab_size=32768, hidden_size=1024, num_layers=12,
                        num_heads=16, ffn_size=4096, max_position=512,
                        dropout=0.0)
        n_req, num_slots, page_size, chunk, cap = 48, 16, 16, 64, 96
        len_set = (16, 32, 48, 64, 96, 128, 192, 256)
        attn_impl = "pallas"
        ttft_budget = 1.0
        # 8 full pages + an 8-token tail: sharing is page-aligned, so
        # followers map the 8 full pages and recompute the tail (a
        # prefix's partial page is completed by the publisher's own
        # suffix before publication, so it never tail-shares)
        shared_prefix_len, shared_tails = 136, (16, 32, 64)
    elif dryrun:
        cfg = GPTConfig.tiny(vocab_size=128, hidden_size=32, num_layers=2,
                             num_heads=2, ffn_size=64, max_position=64,
                             dropout=0.0, attn_impl="xla")
        n_req, num_slots, page_size, chunk, cap = 6, 4, 4, 8, 8
        len_set = (4, 9, 17, 24)
        attn_impl = "lax"
        ttft_budget = 30.0   # smoke box: schema/plumbing, not latency
        shared_prefix_len, shared_tails = 10, (2, 3, 4)   # 2 pages + tail
    else:
        # CPU measurement config: weight-heavy (LLM decode is weight-
        # bound — params >> per-step KV traffic) so batching amortizes
        # weight reads the way real serving does; bf16 KV pages on both
        # sides (generate gets cache_dtype too). Prompt lengths come
        # from a small bucket set, as a shape-bucketing front end would
        # deliver them.
        cfg = GPTConfig(vocab_size=1024, hidden_size=512, num_layers=6,
                        num_heads=8, ffn_size=2048, max_position=320,
                        dropout=0.0, attn_impl="xla")
        n_req, num_slots, page_size, chunk, cap = 32, 8, 16, 64, 64
        len_set = (16, 32, 48, 64, 96, 128, 192, 256)
        attn_impl = "lax"
        ttft_budget = 4.0    # stated CPU SLO: interactive-lane p99 TTFT
        # 8 full pages + an 8-token tail: sharing is page-aligned, so
        # followers map the 8 full pages and recompute the tail (a
        # prefix's partial page is completed by the publisher's own
        # suffix before publication, so it never tail-shares)
        shared_prefix_len, shared_tails = 136, (16, 32, 64)

    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    lens = rng.choice(len_set, n_req)
    prompts = [rng.integers(1, cfg.vocab_size, int(n)).astype(np.int32)
               for n in lens]
    lo, hi = min(len_set), max(len_set)
    cache_dtype = jnp.bfloat16 if not on_tpu else None

    reg = obs.MetricsRegistry()
    # request-lifecycle tracing is ON for the whole bench (ISSUE 10
    # acceptance: the bench emits a Perfetto-loadable .trace.json in
    # which a request's spans reconstruct its full lifecycle) — tracing
    # is host-side only, so the zero-recompile assertions below also
    # prove the invariant holds WITH tracing enabled
    tracer = obs.Tracer(capacity=32768)
    # main mix runs WITHOUT prefix sharing: the prompts are distinct, and
    # the engine-vs-dense comparison must not quietly reuse pages across
    # the two timing passes; sharing is measured by the prefix variant.
    # ttft_budget_s arms the SLO burn-rate monitor over the same budget
    # the percentile keys are judged against.
    eng = serving.ServingEngine(
        model, params, num_slots=num_slots, page_size=page_size,
        max_tokens_per_slot=hi + cap, prefill_chunk=chunk,
        attn_impl=attn_impl, cache_dtype=cache_dtype, registry=reg,
        prefix_sharing=False, tracer=tracer, ttft_budget_s=ttft_budget)
    # startup compiles happen here (every gather bucket + the prefill
    # chunk), so everything timed below is steady-state serving
    eng.warmup()

    # reference prefixes (also an engine warm pass): early stops land in
    # the first few tokens of a greedy stream, so a short prefix rollout
    # is enough to pick each request's EOS id
    ref_new = min(16, cap)
    streams = eng.generate_many(prompts, ref_new, max_steps=1_000_000)
    eos_ids = []
    useful = []
    for i, t in enumerate(streams):
        if i % 6 == 0:          # the no-EOS long tail: run to cap
            eos_ids.append(None)
            useful.append(cap)
            continue
        target = int(rng.integers(2, ref_new))
        first = {}              # token -> first-occurrence index
        for j, tok in enumerate(t.tolist()):
            first.setdefault(tok, j)
        tok, j = min(first.items(), key=lambda kv: abs(kv[1] - target))
        eos_ids.append(int(tok))
        useful.append(j + 1)
    total_useful = int(sum(useful))

    det = obs.RecompileDetector("serving_bench", warmup=0, registry=reg)

    def engine_pass():
        for m in ("serving_ttft_seconds", "serving_queue_wait_seconds",
                  "serving_admit_to_first_token_seconds",
                  "serving_decode_step_seconds",
                  "serving_prefill_step_seconds"):
            reg.unregister(m)   # this pass's samples only
        occ = []
        peak_util = 0.0
        rids = [eng.submit(p, cap, eos_id=e)
                for p, e in zip(prompts, eos_ids)]
        t0 = time.perf_counter()
        while not eng.scheduler.idle():
            eng.step()
            # the gauges hold occupancy/utilization as the decode batch
            # ran (pre-eviction); the cache itself is already drained
            occ.append(reg.gauge("serving_slot_occupancy").value())
            peak_util = max(peak_util,
                            reg.gauge("serving_page_utilization").value())
        dt = time.perf_counter() - t0
        streams = []
        for r, u in zip(rids, useful):
            got = eng.result(r)
            assert got is not None and len(got) == u, \
                "engine/ref divergence"
            streams.append(got)
        ttft_h = reg.histogram("serving_ttft_seconds")
        qw_h = reg.histogram("serving_queue_wait_seconds")
        return {
            "streams": streams,
            "dt": dt,
            "decode_s": reg.histogram("serving_decode_step_seconds"
                                      ).summary()["sum"],
            "prefill_s": reg.histogram("serving_prefill_step_seconds"
                                       ).summary()["sum"],
            "ttft": ttft_h.summary(),
            # TTFT/queue-wait tails (p50/p90/p99): the machine-checkable
            # SLO surface (bucket-interpolated, clamped to observed
            # min/max)
            "ttft_q": {q: ttft_h.quantile(q) for q in (0.5, 0.9, 0.99)},
            "qw_q": {q: qw_h.quantile(q) for q in (0.5, 0.9, 0.99)},
            "a2f_p99": reg.histogram(
                "serving_admit_to_first_token_seconds").quantile(0.99),
            "occ": occ, "peak_util": peak_util,
        }

    # two passes, best wall-clock kept: a 2-core CI box sees ambient
    # load spikes that would otherwise masquerade as engine regressions
    ep = min((engine_pass() for _ in range(2)), key=lambda r: r["dt"])

    # --- SLO probe pass: the same batch burst on the "batch" lane, with
    # interactive probes trickled in WHILE the engine is saturated. The
    # SLO scheduler's priority lanes put a probe at the queue head, so
    # its TTFT is slot-turnover + one prefill chunk — not the whole
    # backlog. ttft_slo_met is judged on the interactive lane: that is
    # the traffic the budget exists for (the batch burst's own TTFT is
    # backlog-dominated by construction and reported separately above).
    probe_interval = 2 if dryrun else 3
    n_probe = max(4, num_slots)
    probe_rids = []
    for p, e in zip(prompts, eos_ids):
        eng.submit(p, cap, eos_id=e, lane="batch")
    steps = 0
    while not eng.scheduler.idle():
        eng.step()
        steps += 1
        if len(probe_rids) < n_probe and steps % probe_interval == 0:
            pr = rng.integers(1, cfg.vocab_size, int(lo)).astype(np.int32)
            probe_rids.append(eng.submit(pr, 8, lane="interactive"))
    probe_ttfts = [eng.request_stats(r)["ttft_s"] for r in probe_rids]
    interactive_p99 = float(np.percentile(probe_ttfts, 99))
    det.check()
    occ, peak_util, ttft = ep["occ"], ep["peak_util"], ep["ttft"]
    dt_engine = ep["dt"]
    eng_decode_s = ep["decode_s"]
    eng_prefill_s = ep["prefill_s"]
    engine_tps = total_useful / max(eng_decode_s, 1e-9)
    engine_e2e = total_useful / dt_engine

    # --- dense loop: same requests through generate(use_cache=True),
    # one call per request (mixed prompt lengths cannot batch correctly
    # through a padded lock-step generate). generate has no EOS exit,
    # so every request decodes the full cap; compile time excluded by a
    # warmup pass over every shape.
    def dense_fn(mnew):
        return jax.jit(lambda pp, ids: model.generate(
            pp, ids, max_new_tokens=mnew, use_cache=True,
            cache_dtype=cache_dtype))

    fns, pf_times = {}, {}
    full = dense_fn(cap)
    pf = dense_fn(1)   # prefill + one token: the dense prefill cost
    for p in prompts:
        if len(p) in fns:
            continue
        x = jnp.asarray(p)[None]
        full(params, x).block_until_ready()         # compile cap graph
        pf(params, x).block_until_ready()           # compile prefill probe
        t0 = time.perf_counter()
        pf(params, x).block_until_ready()
        pf_times[len(p)] = time.perf_counter() - t0
        fns[len(p)] = True
    def dense_pass():
        t0 = time.perf_counter()
        for p in prompts:
            full(params, jnp.asarray(p)[None]).block_until_ready()
        return time.perf_counter() - t0

    dt_dense = min(dense_pass() for _ in range(2))
    # decode-phase split: prefill measured per unique prompt length via
    # the max_new=1 probe (slightly OVERcounts dense prefill — one
    # decode step rides along — so the reported speedup is conservative)
    dense_prefill_s = sum(pf_times[len(p)] for p in prompts)
    dense_decode_s = max(dt_dense - dense_prefill_s, 1e-9)
    dense_tps = total_useful / dense_decode_s
    dense_e2e = total_useful / dt_dense

    speedup = engine_tps / max(dense_tps, 1e-9)
    e2e_speedup = engine_e2e / max(dense_e2e, 1e-9)

    # --- shared-prefix variant: every request carries the same system
    # prompt; prefix sharing must prefill it once (well, once per slot
    # wave — slots admitted before the first publisher finishes cannot
    # share yet) and map the published pages into every follower, so
    # prefill tokens COMPUTED land well under prompt tokens SUBMITTED.
    reg2 = obs.MetricsRegistry()
    eng2 = serving.ServingEngine(
        model, params, num_slots=num_slots, page_size=page_size,
        max_tokens_per_slot=hi + cap, prefill_chunk=chunk,
        attn_impl=attn_impl, cache_dtype=cache_dtype, registry=reg2,
        prefix_sharing=True, tracer=tracer)
    eng2.warmup()
    det2 = obs.RecompileDetector("serving_bench_prefix", warmup=0,
                                 registry=reg2)
    sys_prompt = rng.integers(1, cfg.vocab_size,
                              shared_prefix_len).astype(np.int32)
    # every 4th request repeats an earlier prompt verbatim (regenerate /
    # retry traffic) — THIS is what exercises copy-on-write: once the
    # original has finished and published its final partial page as a
    # tail, the duplicate maps it and must CoW before appending its
    # first decode token. Duplicates prefer a non-page-aligned source
    # (an aligned prompt publishes only full pages — nothing to CoW);
    # an original still in flight when its duplicate is admitted shares
    # full pages only, so cow_copies is demonstrative, not asserted.
    prompts2 = []
    for i, t in enumerate(rng.choice(shared_tails, n_req)):
        if i % 4 == 3:
            cands = [q for q in prompts2 if len(q) % page_size]
            pool = cands or prompts2
            prompts2.append(pool[int(rng.integers(len(pool)))].copy())
        else:
            prompts2.append(np.concatenate(
                [sys_prompt, rng.integers(1, cfg.vocab_size, int(t))
                 .astype(np.int32)]))
    variant_new = min(8, cap)
    t0 = time.perf_counter()
    eng2.generate_many(prompts2, variant_new, max_steps=1_000_000)
    dt_prefix = time.perf_counter() - t0
    det2.check()
    submitted2 = int(sum(len(p) for p in prompts2))
    computed2 = int(reg2.counter("serving_prefill_tokens_total").value())
    shared2 = int(reg2.counter("serving_prefix_shared_tokens_total"
                               ).value())
    ttft2 = reg2.histogram("serving_ttft_seconds")
    prefix_variant = {
        "num_requests": n_req,
        "shared_prefix_len": int(shared_prefix_len),
        "prompt_tokens_submitted": submitted2,
        "prefill_tokens_computed": computed2,
        "prefix_tokens_shared": shared2,
        "prefill_saved_frac": round(1.0 - computed2 / max(submitted2, 1),
                                    4),
        "cow_copies": int(eng2.cache.cow_copies_total),
        "wall_seconds": round(dt_prefix, 3),
        "ttft_p99_s": round(ttft2.quantile(0.99), 6),
        "recompiles": det2.recompiles,
    }

    # --- int8 paged-KV variant (ISSUE 13): the same requests through an
    # int8 page pool with per-token-row scales, attending via the
    # dequant-attend kernels. Tokens may deviate from the bf16 stream
    # only within the quantization quality budget (quant_token_match
    # reports the agreement honestly); throughput is its own stream's
    # tokens over its own decode time, best-of-2 like the baseline.
    reg_q = obs.MetricsRegistry()
    eng_q = serving.ServingEngine(
        model, params, num_slots=num_slots, page_size=page_size,
        max_tokens_per_slot=hi + cap, prefill_chunk=chunk,
        attn_impl=attn_impl, cache_dtype=jnp.int8, registry=reg_q,
        prefix_sharing=False, tracer=obs.Tracer(enabled=False))
    eng_q.warmup(cost_gauges=False)
    det_q = obs.RecompileDetector("serving_bench_int8", warmup=0,
                                  registry=reg_q)

    def quant_pass():
        reg_q.unregister("serving_decode_step_seconds")
        rids_q = [eng_q.submit(p, cap, eos_id=e)
                  for p, e in zip(prompts, eos_ids)]
        while not eng_q.scheduler.idle():
            eng_q.step()
        outs = [eng_q.result(r) for r in rids_q]
        dq = reg_q.histogram("serving_decode_step_seconds"
                             ).summary()["sum"]
        return dq, outs

    qp = min((quant_pass() for _ in range(2)), key=lambda r: r[0])
    det_q.check()
    dq_decode_s, outs_q = qp
    tokens_q = int(sum(len(o) for o in outs_q))
    quant_tps = tokens_q / max(dq_decode_s, 1e-9)
    agree = compared = 0
    for base_t, q_t in zip(ep["streams"], outs_q):
        m = min(len(base_t), len(q_t))
        agree += int((np.asarray(base_t[:m]) == np.asarray(q_t[:m])).sum())
        compared += m
    quant_speedup = quant_tps / max(engine_tps, 1e-9)
    quant_variant = {
        "decode_tokens_per_sec": round(quant_tps, 2),
        "decode_seconds": round(dq_decode_s, 3),
        "tokens": tokens_q,
        "token_match_vs_bf16": round(agree / max(compared, 1), 4),
        "recompiles": det_q.recompiles,
    }

    # --- speculative variant (ISSUE 13): draft proposes spec_k tokens
    # per slot, the target verifies them in ONE batched-prefill-shaped
    # step. Random init has no trained small draft, so the draft IS the
    # target (self-draft): accept rate ~1.0 exercises the long-accept
    # path and the mechanism's overhead honestly. The acceptance GATE:
    # greedy streams must be BIT-EXACT vs the non-speculative engine.
    reg_s = obs.MetricsRegistry()
    eng_s = serving.ServingEngine(
        model, params, num_slots=num_slots, page_size=page_size,
        max_tokens_per_slot=hi + cap, prefill_chunk=chunk,
        attn_impl=attn_impl, cache_dtype=cache_dtype, registry=reg_s,
        tracer=obs.Tracer(enabled=False), draft_model=model,
        draft_params=params, spec_k=4)
    eng_s.warmup(cost_gauges=False)
    det_s = obs.RecompileDetector("serving_bench_spec", warmup=0,
                                  registry=reg_s)

    def spec_pass():
        # the counters are monotonic across passes: report THIS pass's
        # deltas so the committed proposed/accepted match the same
        # single pass the timing and streams come from
        p0 = reg_s.counter("serving_spec_proposed_total").value()
        a0 = reg_s.counter("serving_spec_accepted_total").value()
        reg_s.unregister("serving_decode_step_seconds")
        rids_s = [eng_s.submit(p, cap, eos_id=e)
                  for p, e in zip(prompts, eos_ids)]
        while not eng_s.scheduler.idle():
            eng_s.step()
        outs = [eng_s.result(r) for r in rids_s]
        ds = reg_s.histogram("serving_decode_step_seconds"
                             ).summary()["sum"]
        proposed = reg_s.counter("serving_spec_proposed_total"
                                 ).value() - p0
        accepted = reg_s.counter("serving_spec_accepted_total"
                                 ).value() - a0
        return ds, outs, proposed, accepted

    sp = min((spec_pass() for _ in range(2)), key=lambda r: r[0])
    det_s.check()
    ds_decode_s, outs_s, spec_proposed, spec_accepted = sp
    for base_t, s_t in zip(ep["streams"], outs_s):
        if not np.array_equal(base_t, s_t):
            raise RuntimeError(
                "speculative greedy diverged from non-speculative "
                "greedy — the bit-exactness gate failed")
    spec_accept_rate = spec_accepted / max(spec_proposed, 1)
    tokens_s = int(sum(len(o) for o in outs_s))
    spec_variant = {
        "decode_tokens_per_sec": round(tokens_s /
                                       max(ds_decode_s, 1e-9), 2),
        "decode_seconds": round(ds_decode_s, 3),
        "spec_k": eng_s.spec_k,
        "proposed": int(spec_proposed),
        "accepted": int(spec_accepted),
        "draft": "self (random init has no trained small draft; "
                 "exercises the long-accept path)",
        "exact_vs_nonspeculative": True,
        "recompiles": det_s.recompiles,
    }

    # --- static tokens-per-HBM-byte probe (ISSUE 13 acceptance): lower
    # the decode step of a bf16 and an int8 engine with an identical,
    # KV-dominated pool through the PR 7 cost model, and read each
    # step's KV-cache HBM bytes from the CostReport's argument
    # accounting. tokens_per_hbm_byte = the live tokens the pool hosts
    # per byte of KV HBM the decode step holds — the serving-capacity
    # number the int8 pool doubles (per token: 2x H*Dh bytes bf16 vs
    # H*Dh + 8 scale bytes int8).
    from paddle_tpu import analysis
    from paddle_tpu.models.gpt import GPTConfig as _Cfg
    pcfg = _Cfg(vocab_size=256, hidden_size=128, num_layers=2,
                num_heads=4, ffn_size=256, max_position=1024,
                dropout=0.0, attn_impl="xla")
    pmodel = GPT(pcfg)
    pparams = pmodel.init(jax.random.PRNGKey(2))
    p_pages, p_ps = 2049, 16

    def probe(dtype):
        engp = serving.ServingEngine(
            pmodel, pparams, num_slots=8, page_size=p_ps,
            max_tokens_per_slot=512, num_pages=p_pages,
            attn_impl="lax", cache_dtype=dtype, decode_block=8)
        c = engp.cache.config
        pages_abs = analysis.abstractify(engp.cache.pages)
        args = (analysis.abstractify(engp.params), pages_abs,
                jax.ShapeDtypeStruct((8, 8), jnp.int32),
                jax.ShapeDtypeStruct((8,), jnp.int32),
                jax.ShapeDtypeStruct((8,), jnp.int32),
                jax.ShapeDtypeStruct((8,), jnp.int32))
        cost = analysis.estimate_cost(engp.decode_step, *args,
                                      name=f"decode_{dtype}")
        import math as _math
        kv_bytes = sum(
            _math.prod(a.shape) * jnp.dtype(a.dtype).itemsize
            for a in jax.tree_util.tree_leaves(pages_abs))
        # sanity: the KV pool really is inside the step's arg bytes
        assert cost.arg_bytes > kv_bytes > 0
        capacity_tokens = (c.num_pages - 1) * c.page_size
        return capacity_tokens / kv_bytes, cost

    tpb_int8, cost_int8 = probe(jnp.int8)
    tpb_bf16, cost_bf16 = probe(jnp.bfloat16)
    quant_static_ratio = tpb_int8 / tpb_bf16
    if quant_static_ratio < 1.8:
        raise RuntimeError(
            f"static tokens-per-HBM-byte ratio {quant_static_ratio:.3f} "
            "< 1.8x the bf16 baseline — the int8 pool lost its bytes "
            "advantage")

    # --- trace canary: a tiny engine with a deliberately starved page
    # pool + an EDF-boosted deadline, so the exported timeline ALWAYS
    # carries scheduler-decision annotations (sched_skip / sched_boost)
    # next to the measured passes' request lifecycles — the decisions
    # depend on saturation timing in the measured mix, the canary makes
    # them deterministic. Runs after det/det2.check(), on its own
    # registry, so its compiles never pollute the recompile accounting.
    ccfg = GPTConfig.tiny(vocab_size=64, hidden_size=16, num_layers=1,
                          num_heads=2, ffn_size=32, max_position=32,
                          dropout=0.0, attn_impl="xla")
    cmodel = GPT(ccfg)
    cparams = cmodel.init(jax.random.PRNGKey(1))
    eng3 = serving.ServingEngine(
        cmodel, cparams, num_slots=2, page_size=4,
        max_tokens_per_slot=16, num_pages=5, prefill_chunk=4,
        attn_impl="lax", registry=obs.MetricsRegistry(), tracer=tracer,
        prefix_sharing=False)
    eng3.warmup(cost_gauges=False)
    canary = np.arange(1, 9, dtype=np.int32)
    eng3.submit(canary, 8)                   # takes all 4 usable pages
    eng3.scheduler.note_ttft(10.0)           # seed the TTFT estimator
    # deadline < EWMA estimate -> at-risk -> sched_boost; no pages while
    # the first request runs -> sched_skip per admission pass
    eng3.submit(canary, 8, lane="interactive", ttft_deadline_s=5.0)
    csteps = 0
    while not eng3.scheduler.idle():
        eng3.step()
        csteps += 1
        if csteps > 10_000:
            raise RuntimeError("trace canary did not converge")

    # --- trace artifact: self-validate the Perfetto contract + the
    # lifecycle-reconstruction acceptance before writing it next to
    # BENCH_SERVING.json
    all_spans = tracer.spans()          # one ring snapshot, then index
    req_spans = [s for s in all_spans if s.name == "serving.request"]
    traces_by_name = {}
    for s in all_spans:
        traces_by_name.setdefault(s.name, set()).add(s.trace_id)
    ev_names = {e[1] for s in req_spans for e in s.events}
    for needed in ("submitted", "admitted", "first_token", "finished",
                   "prefix_shared", "sched_skip", "sched_boost"):
        if needed not in ev_names:
            raise RuntimeError(
                f"trace self-check: no {needed!r} event in any "
                "serving.request span")
    full = [s for s in req_spans if s.end is not None
            and s.trace_id in traces_by_name.get("serving.prefill_chunk",
                                                 ())
            and s.trace_id in traces_by_name.get("serving.decode_block",
                                                 ())]
    if not full:
        raise RuntimeError("trace self-check: no request trace "
                           "reconstructs queue->prefill->decode->finish")
    chrome = tracer.to_chrome()
    obs.chrome_trace_valid(chrome, require_events=len(full))
    jpath = serving_json_path(dryrun)
    trace_path = (jpath[:-5] if jpath.endswith(".json") else jpath) \
        + ".trace.json"
    with open(trace_path, "w") as f:
        json.dump(chrome, f)

    ttft_p = ep["ttft_q"]
    qw_p = ep["qw_q"]
    result = {
        "metric": "serving_decode_tokens_per_sec",
        "value": round(engine_tps, 2),
        "unit": "tokens/s",
        "vs_baseline": round(speedup / 2.0, 4),  # 1.0 == the 2x target
        "decode_tokens_per_sec": round(engine_tps, 2),
        "baseline_tokens_per_sec": round(dense_tps, 2),
        "speedup_vs_dense_loop": round(speedup, 4),
        "end_to_end_tokens_per_sec": round(engine_e2e, 2),
        "end_to_end_speedup": round(e2e_speedup, 4),
        "decode_seconds_engine": round(eng_decode_s, 3),
        "decode_seconds_dense": round(dense_decode_s, 3),
        "prefill_seconds_engine": round(eng_prefill_s, 3),
        "prefill_seconds_dense": round(dense_prefill_s, 3),
        "ttft_mean_s": round(ttft.get("mean", 0.0), 6),
        "ttft_max_s": round(ttft.get("max", 0.0), 6),
        "ttft_p50_s": round(ttft_p[0.5], 6),
        "ttft_p90_s": round(ttft_p[0.9], 6),
        "ttft_p99_s": round(ttft_p[0.99], 6),
        "ttft_interactive_p99_s": round(interactive_p99, 6),
        "ttft_budget_s": ttft_budget,
        "ttft_slo_met": bool(interactive_p99 <= ttft_budget),
        "queue_wait_p50_s": round(qw_p[0.5], 6),
        "queue_wait_p90_s": round(qw_p[0.9], 6),
        "queue_wait_p99_s": round(qw_p[0.99], 6),
        "admit_to_first_token_p99_s": round(ep["a2f_p99"], 6),
        # burn-rate monitor state at bench end: the burst mix BLOWS the
        # interactive budget by construction (batch-lane TTFT is
        # backlog-dominated), so a nonzero alert count here is the
        # monitor working, not a failure
        "slo_burn_rate": round(eng.slo_monitor.burn["fast"], 4),
        "slo_alerts_total": eng.slo_monitor.alerts_total,
        "trace_json": trace_path,
        "trace_spans": len(tracer.spans()),
        "prefix_variant": prefix_variant,
        # ISSUE 13: quantized pool + speculative decoding. The static
        # keys come from the cost model (deterministic); the measured
        # keys are this box's wall clock, best-of-2.
        "tokens_per_hbm_byte": round(tpb_int8, 9),
        "tokens_per_hbm_byte_bf16": round(tpb_bf16, 9),
        "quant_static_bytes_ratio": round(quant_static_ratio, 4),
        "quant_speedup": round(quant_speedup, 4),
        "quant_variant": quant_variant,
        "spec_accept_rate": round(spec_accept_rate, 4),
        "spec_variant": spec_variant,
        "mean_slot_occupancy": round(float(np.mean(occ)), 4),
        "page_utilization_peak": round(peak_util, 4),
        "decode_recompiles_after_warmup": det.recompiles,
        "num_requests": n_req,
        "num_slots": num_slots,
        "page_size": page_size,
        "decode_cap": cap,
        "useful_tokens": total_useful,
        "mean_useful_per_request": round(total_useful / n_req, 2),
        "prompt_lens": [int(lo), int(hi)],
        "device": getattr(dev, "device_kind", dev.platform),
        "dryrun": bool(dryrun),
        "_telemetry": {"steps": len(occ), "dt": dt_engine,
                       "examples_per_step": num_slots,
                       "tokens_per_step": total_useful / max(len(occ), 1)},
    }

    missing = [k for k in SERVING_SCHEMA if k not in result]
    if missing:
        raise RuntimeError(f"BENCH_SERVING schema self-check failed: "
                           f"missing {missing}")
    if result["decode_recompiles_after_warmup"] != 0:
        raise RuntimeError("steady-state serving recompiled "
                           f"{det.recompiles}x — fixed-shape invariant "
                           "broken (decode or prefill bucket missed by "
                           "warmup)")
    if prefix_variant["recompiles"] != 0:
        raise RuntimeError("prefix-sharing variant recompiled "
                           f"{prefix_variant['recompiles']}x — CoW/"
                           "prefill shapes drifted")
    if quant_variant["recompiles"] != 0:
        raise RuntimeError("int8 variant recompiled "
                           f"{quant_variant['recompiles']}x — the "
                           "quantized decode/prefill buckets drifted")
    if spec_variant["recompiles"] != 0:
        raise RuntimeError("speculative variant recompiled "
                           f"{spec_variant['recompiles']}x — a "
                           "draft/verify bucket missed warmup")
    if not dryrun and quant_speedup < 1.0:
        raise RuntimeError(
            f"int8 decode tokens/s regressed vs the bf16 baseline "
            f"({quant_speedup:.3f}x) — the quantized path must be no "
            "worse on this box")
    import os
    path = serving_json_path(dryrun)
    committed = {k: v for k, v in result.items() if k != "_telemetry"}
    # the checked-in artifact must be portable across checkouts: the
    # trace sits next to this JSON, so record the basename (the stdout
    # result keeps the absolute path for run_ci / tooling)
    committed["trace_json"] = os.path.basename(trace_path)
    with open(path, "w") as f:
        json.dump(committed, f, indent=2)
    result["bench_json"] = path
    return result


KERNELS_SCHEMA = ("metric", "value", "unit", "vs_baseline", "kernels",
                  "impl", "tuner_cache_hits", "tuner_cache_misses",
                  "tuner_stale_entries", "committed_cache_entries",
                  "committed_cache_stale", "device", "dryrun")


def kernels_json_path(dryrun: bool) -> str:
    import os
    if dryrun:  # CI smoke must not dirty the checkout
        return os.environ.get("PADDLE_TPU_BENCH_KERNELS",
                              "/tmp/BENCH_KERNELS.json")
    return os.environ.get(
        "PADDLE_TPU_BENCH_KERNELS",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_KERNELS.json"))


def disagg_json_path(dryrun: bool) -> str:
    import os
    if dryrun:  # CI smoke must not dirty the checkout
        return os.environ.get("PADDLE_TPU_BENCH_DISAGG",
                              "/tmp/BENCH_DISAGG.json")
    return os.environ.get(
        "PADDLE_TPU_BENCH_DISAGG",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_DISAGG.json"))


def run_bench_disagg(dev, dryrun=False):
    """Prefill/decode disaggregation (ISSUE 19 acceptance): a
    flops-bound prefill tier streaming pages into a KV-bound decode
    tier, against the colocated fleet it replaces, under the SAME
    saturating mixed burst.

    Two fleets, identical chips (2 replicas each), identical workload:

    - **colocated** — two ordinary replicas; every slot is held for
      its request's ENTIRE decode, so a burst of long decodes pins
      every slot and interactive prompts queue behind them.
    - **disaggregated** — one ``tier="prefill"`` replica (slot-light:
      slots churn at prefill speed) streaming each prefill-complete
      slot to one ``tier="decode"`` replica (slot-heavy: sized for KV
      capacity, the provisioning freedom disaggregation buys). The
      handoff is the sha256-verified per-(page, tp-shard) shard
      manifest — the exact ``snapshot_slot``/``restore_slot``
      migration format.

    The workload is a background wave of long decodes saturating every
    colocated slot, with short interactive prompts injected while it
    runs. Reported gates (hard non-dryrun):

    - interactive TTFT p99: colocated degrades to ~the background
      decode time (queue wait for a slot), the prefill tier stays flat
      — the ratio must be >= 2x;
    - decode tokens/s by busy-time accounting (tokens / the engines'
      ``serving_decode_step_seconds`` histogram sum): the decode tier
      must be within 10% of colocated (>= 0.9x);
    - transfer bytes: counted from ``fleet_handoff_bytes_total`` and
      budget-gated against pages_for(max_tokens) * page_bytes per
      handoff;
    - ZERO steady-state recompiles on BOTH tiers (every engine fully
      warmed through its tier-filtered ``warmup_plan`` first), with
      per-tier bucket coverage (plan superset of reachable).

    Background outputs must also be bit-identical across the two
    fleets (greedy determinism survives the handoff). Emits
    BENCH_DISAGG.json (schema self-validated) next to this file
    (dryrun: /tmp)."""
    import numpy as np

    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.serving import fleet
    from paddle_tpu.models.gpt import GPT, GPTConfig

    if dryrun:
        cfg = GPTConfig.tiny(vocab_size=128, hidden_size=32,
                             num_layers=2, num_heads=2, ffn_size=64,
                             max_position=128, dropout=0.0,
                             attn_impl="xla")
        page_size, chunk = 4, 8
        bg_n, bg_cap, bg_lens = 4, 12, (9, 12)
        int_n, int_cap, int_len = 4, 4, 5
        colo_slots, pre_slots, dec_slots = 2, 2, 8
        interactive_every = 2
    else:
        # CPU measurement config: background decodes long enough that
        # colocated slot-wait dominates interactive TTFT; the decode
        # tier sized so the whole background wave PLUS the interactive
        # overlap fit without in-place fallback — but no larger: the
        # decode step is a fixed num_slots-lane shape, so every slot
        # beyond the live wave is padded work the busy-time throughput
        # gate charges against the disaggregated fleet
        cfg = GPTConfig(vocab_size=512, hidden_size=192, num_layers=3,
                        num_heads=4, ffn_size=768, max_position=256,
                        dropout=0.0, attn_impl="xla")
        page_size, chunk = 16, 32
        bg_n, bg_cap, bg_lens = 8, 48, (24, 40, 56)
        int_n, int_cap, int_len = 8, 8, 16
        colo_slots, pre_slots, dec_slots = 4, 4, 12
        interactive_every = 3
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    # identical per-slot token budget everywhere: the migration format
    # reserves prompt+budget on restore, so the decode tier must honor
    # the same cap the prefill tier admitted under
    max_tok = max(bg_lens) + bg_cap
    bg_prompts = [rng.integers(1, cfg.vocab_size,
                               int(n)).astype(np.int32)
                  for n in rng.choice(bg_lens, bg_n)]
    int_prompts = [rng.integers(1, cfg.vocab_size,
                                int_len).astype(np.int32)
                   for _ in range(int_n)]

    def make_replica(name, tier, slots):
        eng = serving.ServingEngine(
            model, params, num_slots=slots, page_size=page_size,
            max_tokens_per_slot=max_tok, prefill_chunk=chunk,
            attn_impl="lax", registry=obs.MetricsRegistry(), tier=tier)
        # per-tier bucket coverage: the tier-filtered warmup plan must
        # reach every signature the tier can execute
        plan = set(eng.warmup_plan())
        reach = eng.reachable_signatures()
        if not plan >= reach:
            raise RuntimeError(
                f"{tier} tier bucket coverage hole: {reach - plan}")
        return fleet.LocalReplica(eng, name=name).warmup()

    def decode_busy(replicas):
        return sum(float(r.engine._reg.histogram(
            "serving_decode_step_seconds").summary()["sum"])
            for r in replicas)

    t_bench0 = time.perf_counter()

    def mixed_burst(replicas, reg):
        router = fleet.FleetRouter(replicas, policy="p2c",
                                   registry=reg, seed=5)
        busy0 = decode_busy(replicas)
        bg = [router.submit(p, bg_cap) for p in bg_prompts]
        inter, steps, nsub = [], 0, 0
        while not router.idle() or nsub < int_n:
            router.step()
            steps += 1
            if steps % interactive_every == 0 and nsub < int_n:
                inter.append(router.submit(int_prompts[nsub], int_cap,
                                           lane="interactive"))
                nsub += 1
            if steps > 1_000_000:
                raise RuntimeError("disagg burst did not converge")
        outs = [router.result(f) for f in bg]
        stats = [router.request_stats(f) for f in inter]
        if any(o is None for o in outs) or any(s is None
                                               for s in stats):
            raise RuntimeError("mixed burst lost a request")
        ttfts = [float(s["ttft_s"]) for s in stats]
        tokens = float(bg_n * bg_cap + int_n * int_cap)
        tps = tokens / max(decode_busy(replicas) - busy0, 1e-9)
        return router, outs, ttfts, tps, steps

    # --- colocated leg
    colo = [make_replica(f"c{i}", "colocated", colo_slots)
            for i in range(2)]
    reg_c = obs.MetricsRegistry()
    _, outs_c, ttfts_c, tps_c, steps_c = mixed_burst(colo, reg_c)

    # --- disaggregated leg
    pre = make_replica("p0", "prefill", pre_slots)
    dec = make_replica("d0", "decode", dec_slots)
    reg_d = obs.MetricsRegistry()
    router_d, outs_d, ttfts_d, tps_d, steps_d = mixed_burst(
        [pre, dec], reg_d)

    if not all(np.array_equal(a, b)
               for a, b in zip(outs_c, outs_d)):
        raise RuntimeError("disaggregated greedy tokens diverged "
                           "from the colocated fleet")
    for rep, tier in ((colo[0], "colocated"), (colo[1], "colocated"),
                      (pre, "prefill"), (dec, "decode")):
        n = rep.engine.recompile_detector.recompiles
        if n:
            raise RuntimeError(
                f"{tier} replica {rep.name} recompiled {n}x in "
                "steady state after warmup")

    # --- handoff transfer accounting, budget-gated
    fh = router_d.health()
    handoffs = int(fh["handoffs_total"])
    transfer_bytes = float(reg_d.counter(
        "fleet_handoff_bytes_total",
        "sha256-verified page bytes shipped prefill -> "
        "decode").value(src="p0", dst="d0"))
    c = dec.engine.cache.config
    page_bytes = (dec.engine.cache.pages.nbytes // c.num_pages
                  if hasattr(dec.engine.cache.pages, "nbytes")
                  else sum(int(p.nbytes) for p in jax.tree_util
                           .tree_leaves(dec.engine.cache.pages))
                  // c.num_pages)
    transfer_budget = float(handoffs * c.pages_for(max_tok)
                            * page_bytes)
    if handoffs < bg_n:
        raise RuntimeError(
            f"only {handoffs} handoffs for {bg_n} background "
            "requests — the prefill tier is not streaming")
    if not 0.0 < transfer_bytes <= transfer_budget:
        raise RuntimeError(
            f"handoff transfer {transfer_bytes:.0f}B outside the "
            f"(0, {transfer_budget:.0f}B] budget")

    ttft_p99_c = float(np.percentile(ttfts_c, 99))
    ttft_p99_d = float(np.percentile(ttfts_d, 99))
    ttft_ratio = ttft_p99_c / max(ttft_p99_d, 1e-9)
    tput_ratio = tps_d / max(tps_c, 1e-9)
    if not dryrun:
        if ttft_ratio < 2.0:
            raise RuntimeError(
                f"disagg TTFT p99 improvement {ttft_ratio:.2f}x "
                "< the 2x acceptance floor")
        if tput_ratio < 0.9:
            raise RuntimeError(
                f"disagg decode throughput {tput_ratio:.2f}x of "
                "colocated — below the 0.9x (within-10%) floor")

    result = {
        "metric": "serving_disagg_ttft_p99_improvement",
        "value": round(ttft_ratio, 3),
        "unit": "x vs colocated (mixed burst)",
        "vs_baseline": round(ttft_ratio / 2.0, 3),
        "ttft_interactive_p99_s": {
            "colocated": round(ttft_p99_c, 4),
            "disaggregated": round(ttft_p99_d, 4)},
        "ttft_ratio": round(ttft_ratio, 3),
        "decode_tokens_per_s_busy": {
            "colocated": round(tps_c, 2),
            "disaggregated": round(tps_d, 2)},
        "throughput_ratio": round(tput_ratio, 3),
        "greedy_identical": True,
        "recompiles_after_warmup": {"prefill": 0, "decode": 0,
                                    "colocated": 0},
        "handoffs": handoffs,
        "handoff_fallbacks_in_place": int(
            0 if reg_d.get("fleet_handoff_fallback_total") is None
            else reg_d.get("fleet_handoff_fallback_total").value(
                replica="p0")),
        "transfer_bytes": int(transfer_bytes),
        "transfer_budget_bytes": int(transfer_budget),
        "transfer_bytes_per_handoff": round(
            transfer_bytes / max(handoffs, 1), 1),
        "tiers": {"prefill": {"slots": pre_slots},
                  "decode": {"slots": dec_slots},
                  "colocated": {"slots": colo_slots, "replicas": 2}},
        "workload": {"background": bg_n, "background_cap": bg_cap,
                     "interactive": int_n, "interactive_cap": int_cap,
                     "prompt_lens": sorted(set(int(n) for n in bg_lens)),
                     "interactive_len": int_len},
        "steps": {"colocated": steps_c, "disaggregated": steps_d},
        "bench_wall_s": round(time.perf_counter() - t_bench0, 1),
        "device": str(dev.device_kind if hasattr(dev, "device_kind")
                      else dev.platform),
        "dryrun": bool(dryrun),
    }
    # schema self-check before the file lands
    for k in ("ttft_interactive_p99_s", "ttft_ratio",
              "decode_tokens_per_s_busy", "throughput_ratio",
              "greedy_identical", "recompiles_after_warmup",
              "handoffs", "transfer_bytes", "transfer_budget_bytes"):
        if k not in result:
            raise RuntimeError(f"BENCH_DISAGG schema self-check "
                               f"failed: missing {k}")
    path = disagg_json_path(dryrun)
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    result["json"] = path
    return result


def prefix_fleet_json_path(dryrun: bool) -> str:
    import os
    if dryrun:  # CI smoke must not dirty the checkout
        return os.environ.get("PADDLE_TPU_BENCH_PREFIX_FLEET",
                              "/tmp/BENCH_PREFIX_FLEET.json")
    return os.environ.get(
        "PADDLE_TPU_BENCH_PREFIX_FLEET",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_PREFIX_FLEET.json"))


def run_bench_prefix_fleet(dev, dryrun=False):
    """Hierarchical KV (ISSUE 20 acceptance): host-spilled cold pages
    plus fleet-global prefix fetch, against the affinity-only router
    it extends, under the SAME shared-prefix workload with scale-out
    AND scale-in churn.

    Two fleets, identical chips and identical traffic:

    - **affinity-only** — ``prefix_fetch=False, host_spill_pages=0``:
      routing chases the prefix holder, but a miss (or an evicted
      page) re-prefills from scratch, and a drained holder takes its
      prefix pages to the grave.
    - **hierarchical** — allocator pressure spills published pages to
      a pinned host pool (restored byte-identical on the next hit),
      and a replica that misses a prefix a peer advertises imports
      the committed pages as hash-verified migration shards instead
      of recomputing them.

    The churn script (identical in both legs): wave A publishes the
    shared prefixes and adds filler pressure on a 2-replica fleet; a
    THIRD warmed replica scales out; every prefix holder starts
    draining (drain refuses new work, so wave B must route to the
    non-holders — the hierarchical leg fetches, the baseline
    re-prefills); the holders are then drain-removed (scale-in) and
    wave C runs on the survivors.

    Headline metric: fleet prefill tokens actually COMPUTED per
    served token (``serving_prefill_tokens_total`` summed over every
    engine that ever served, divided by ``serving_tokens_total`` —
    lower is better). Gates (hard non-dryrun):

    - the hierarchical fleet must be STRICTLY below affinity-only;
    - greedy outputs bit-identical across the two legs (sharing and
      fetching never change tokens);
    - ZERO steady-state recompiles on every replica in BOTH legs
      (spill/restore and page import ride the warmed
      ``("page_read",)``/``("page_write",)`` signatures);
    - the hierarchical leg actually exercised BOTH tiers: fetched
      pages > 0 and spilled pages > 0.

    Emits BENCH_PREFIX_FLEET.json (schema self-validated) next to
    this file (dryrun: /tmp)."""
    import numpy as np

    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.serving import fleet
    from paddle_tpu.serving.paged_cache import prompt_prefix_digests
    from paddle_tpu.models.gpt import GPT, GPTConfig

    if dryrun:
        cfg = GPTConfig.tiny(vocab_size=64, hidden_size=16,
                             num_layers=2, num_heads=2, ffn_size=32,
                             max_position=64, dropout=0.0,
                             attn_impl="xla")
        reqs_per_prefix = 2
    else:
        cfg = GPTConfig.tiny(vocab_size=256, hidden_size=64,
                             num_layers=2, num_heads=4, ffn_size=128,
                             max_position=64, dropout=0.0,
                             attn_impl="xla")
        reqs_per_prefix = 3
    page_size, prefix_len, cap = 4, 16, 6
    num_pages, spill_pages = 14, 8
    filler_len = 24
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    vocab = cfg.vocab_size
    prefixes = [rng.integers(1, vocab, prefix_len).astype(np.int32)
                for _ in range(2)]
    prefix_digs = set()
    for pre in prefixes:
        prefix_digs.update(prompt_prefix_digests(pre, page_size))

    def shared(pre):
        tail = rng.integers(1, vocab,
                            int(rng.integers(2, 5))).astype(np.int32)
        return np.concatenate([pre, tail])

    def filler():
        return rng.integers(1, vocab, filler_len).astype(np.int32)

    # one deterministic prompt script, replayed by BOTH legs
    wave_a = [shared(p) for p in prefixes
              for _ in range(reqs_per_prefix)] + [filler(), filler()]
    wave_b = [shared(p) for p in prefixes
              for _ in range(reqs_per_prefix)] + [filler()]
    wave_c = [shared(p) for p in prefixes
              for _ in range(reqs_per_prefix)]
    served_cap = cap * (len(wave_a) + len(wave_b) + len(wave_c))

    t_bench0 = time.perf_counter()

    def make_replica(name, spill):
        eng = serving.ServingEngine(
            model, params, num_slots=2, page_size=page_size,
            num_pages=num_pages, max_tokens_per_slot=44,
            prefill_chunk=page_size, attn_impl="lax",
            registry=obs.MetricsRegistry(), host_spill_pages=spill)
        return fleet.LocalReplica(eng, name=name).warmup()

    def run_wave(router, prompts, outs):
        frids = [router.submit(p, cap) for p in prompts]
        router.run_until_idle(max_steps=200_000)
        for f in frids:
            o = router.result(f)
            if o is None:
                raise RuntimeError("prefix_fleet wave lost a request")
            outs.append(o)

    def leg(prefix_fetch, spill):
        reps = [make_replica(f"r{i}", spill) for i in range(2)]
        reg = obs.MetricsRegistry()
        router = fleet.FleetRouter(reps, policy="affinity",
                                   registry=reg, seed=9,
                                   prefix_fetch=prefix_fetch)
        all_reps = list(reps)
        outs = []
        run_wave(router, wave_a, outs)
        # scale-out churn: a fresh warmed replica joins mid-traffic
        extra = make_replica("r2", spill)
        router.add_replica(extra)
        all_reps.append(extra)
        # every prefix holder starts draining — wave B MUST land on
        # replicas that never saw the prefixes (drain refuses new
        # work, but exporting committed pages is a read)
        holders = [r for r in reps
                   if prefix_digs & set(r.prefix_digests())]
        if not holders:
            raise RuntimeError("wave A published no shared prefix")
        for h in holders:
            h.draining = True
        run_wave(router, wave_b, outs)
        # scale-in churn: the holders leave the fleet for good
        for h in holders:
            router.drain_replica(h, remove=True)
        run_wave(router, wave_c, outs)
        prefill = sum(float(r.engine._reg.counter(
            "serving_prefill_tokens_total").value()) for r in all_reps)
        served = sum(float(r.engine._reg.counter(
            "serving_tokens_total").value()) for r in all_reps)
        shared_tok = sum(float(r.engine._reg.counter(
            "serving_prefix_shared_tokens_total").value())
            for r in all_reps)
        recompiles = sum(int(r.engine.recompile_detector.recompiles)
                         for r in all_reps)
        spilled = sum(int(r.engine.cache.spill_pool.spilled_total)
                      for r in all_reps if r.engine.cache.spill_pool)
        spilled_bytes = sum(
            int(r.engine.cache.spill_pool.spilled_bytes_total)
            for r in all_reps if r.engine.cache.spill_pool)
        restored = sum(int(r.engine.cache.spill_pool.restored_total)
                       for r in all_reps if r.engine.cache.spill_pool)
        return {
            "outs": outs, "router_reg": reg,
            "prefill_tokens": prefill, "served_tokens": served,
            "prefill_per_served": prefill / max(served, 1e-9),
            "shared_tokens": shared_tok,
            "prefix_hit_rate": round(
                shared_tok / max(prefill + shared_tok, 1e-9), 4),
            "recompiles": recompiles,
            "spilled_pages": spilled, "spilled_bytes": spilled_bytes,
            "restored_pages": restored,
        }

    base = leg(prefix_fetch=False, spill=0)
    hier = leg(prefix_fetch=True, spill=spill_pages)

    if not all(np.array_equal(a, b)
               for a, b in zip(base["outs"], hier["outs"])):
        raise RuntimeError("hierarchical greedy tokens diverged from "
                           "the affinity-only fleet")
    if base["recompiles"] or hier["recompiles"]:
        raise RuntimeError(
            f"steady-state recompiles after warmup: affinity-only="
            f"{base['recompiles']} hierarchical={hier['recompiles']}")
    hreg = hier["router_reg"]
    fetched_pages = int(hreg.counter(
        "fleet_prefix_fetch_pages_total").value())
    fetched_bytes = int(hreg.counter(
        "fleet_prefix_fetch_bytes_total").value())
    degraded = int(hreg.counter(
        "fleet_prefix_fetch_degraded_total").value())
    ratio = (base["prefill_per_served"]
             / max(hier["prefill_per_served"], 1e-9))
    if not dryrun:
        if hier["prefill_per_served"] >= base["prefill_per_served"]:
            raise RuntimeError(
                f"hierarchical prefill/served "
                f"{hier['prefill_per_served']:.3f} not strictly below "
                f"affinity-only {base['prefill_per_served']:.3f}")
        if fetched_pages <= 0:
            raise RuntimeError("fleet prefix fetch never fired")
        if hier["spilled_pages"] <= 0:
            raise RuntimeError("host spill tier never engaged")

    result = {
        "metric": "prefix_fleet_prefill_tokens_per_served_token",
        "value": round(hier["prefill_per_served"], 4),
        "unit": "prefill tokens/served token (lower is better)",
        "vs_baseline": round(ratio, 3),
        "prefill_per_served": {
            "affinity_only": round(base["prefill_per_served"], 4),
            "hierarchical": round(hier["prefill_per_served"], 4)},
        "prefill_tokens": {
            "affinity_only": int(base["prefill_tokens"]),
            "hierarchical": int(hier["prefill_tokens"])},
        "served_tokens": {
            "affinity_only": int(base["served_tokens"]),
            "hierarchical": int(hier["served_tokens"])},
        "prefix_hit_rate": {
            "affinity_only": base["prefix_hit_rate"],
            "hierarchical": hier["prefix_hit_rate"]},
        "fetch": {"pages": fetched_pages, "bytes": fetched_bytes,
                  "degraded": degraded},
        "spill": {"spilled_pages": hier["spilled_pages"],
                  "spilled_bytes": hier["spilled_bytes"],
                  "restored_pages": hier["restored_pages"]},
        "greedy_identical": True,
        "recompiles_after_warmup": {
            "affinity_only": base["recompiles"],
            "hierarchical": hier["recompiles"]},
        "churn": {"scale_out_replicas": 1, "drained_holders": True},
        "workload": {"prefixes": len(prefixes),
                     "prefix_len": prefix_len,
                     "requests": (len(wave_a) + len(wave_b)
                                  + len(wave_c)),
                     "cap": cap, "served_cap": served_cap,
                     "filler_len": filler_len},
        "bench_wall_s": round(time.perf_counter() - t_bench0, 1),
        "device": str(dev.device_kind if hasattr(dev, "device_kind")
                      else dev.platform),
        "dryrun": bool(dryrun),
    }
    # schema self-check before the file lands
    for k in ("prefill_per_served", "prefill_tokens", "served_tokens",
              "prefix_hit_rate", "fetch", "spill", "greedy_identical",
              "recompiles_after_warmup", "churn"):
        if k not in result:
            raise RuntimeError(f"BENCH_PREFIX_FLEET schema "
                               f"self-check failed: missing {k}")
    path = prefix_fleet_json_path(dryrun)
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    result["json"] = path
    return result


def run_bench_kernels(dev, dryrun=False):
    """Shared kernel-layer microbench (ISSUE 12 acceptance): for every
    registered single-device kernel (flash attention, ragged paged
    decode, ragged paged prefill — ring inherits the flash inner blocks)
    measure autotuned vs default block sizes across the kernel's 3
    sample shape buckets, through ONE harness: ``kernels.dispatch`` with
    an explicit candidate override, timed on the live backend (Pallas on
    TPU, the same kernels under the interpreter on CPU). Then assert the
    tuner-cache contract: a measured entry is a HIT on the next
    resolution of the same bucket, and the committed
    ``tools/kernel_tune.json`` loads with zero stale entries (a contract
    version bump without a reseed fails the bench, not the user). A
    non-dryrun run MERGES its measured winners into the committed cache
    (keys carry the device kind, so a TPU session refreshes TPU entries
    without touching the CPU-CI ones) — commit the updated manifest with
    the session. Emits BENCH_KERNELS.json (schema self-validated) next
    to this file (dryrun: /tmp, cache untouched)."""
    import numpy as np

    from paddle_tpu import kernels

    kernels.load_all()
    on_tpu = dev.platform == "tpu"
    impl = "pallas" if on_tpu else "pallas_interpret"
    reps = 5 if on_tpu else 1
    tuner = kernels.KernelTuner(path=None)    # cold: measure fresh
    leaf = [n for n in kernels.names()
            if kernels.get(n).contract.block_candidates
            and not kernels.get(n).requires_mesh]
    per_kernel = {}
    speedups = []
    t_bench0 = time.perf_counter()
    for name in leaf:
        spec = kernels.get(name)
        buckets = {}
        for seed in (0, 1, 2):
            args, kw = spec.sample_inputs(seed)
            res = tuner.measure(spec, args, kw, impl=impl, reps=reps)
            speedup = res["default_s"] / max(res["best_s"], 1e-9)
            speedups.append(speedup)
            buckets[kernels.tune_key(spec, args, kw)] = {
                "default_blocks": res["default_blocks"],
                "tuned_blocks": res["blocks"],
                "default_s": round(res["default_s"], 6),
                "tuned_s": round(res["best_s"], 6),
                "speedup_vs_default": round(speedup, 3),
            }
        per_kernel[name] = buckets

    # tuner-cache hit contract: the bucket just measured must resolve
    # from cache (not re-derive a prior) on the next dispatch
    for name in leaf:
        spec = kernels.get(name)
        args, kw = spec.sample_inputs(0)
        hits_before = tuner.hits
        blocks = tuner.get(spec, args, kw)
        if tuner.hits != hits_before + 1:
            raise RuntimeError(
                f"tuner cache MISSED a just-measured bucket for {name} "
                f"(stats {tuner.stats()}) — key derivation is not "
                "deterministic")
        key = kernels.tune_key(spec, args, kw)
        if blocks != tuner.entries[key]["blocks"]:
            raise RuntimeError(f"cache returned foreign blocks for {key}")

    # committed-manifest round trip: loads, and nothing in it is stale.
    # Validate BEFORE any write — a failing gate must not leave the
    # checkout with a rewritten (still-failing) manifest.
    committed = kernels.KernelTuner(kernels.DEFAULT_CACHE_PATH)
    committed_stale = len(committed.stale_entries())
    if committed_stale:
        raise RuntimeError(
            f"tools/kernel_tune.json has {committed_stale} stale "
            "entr(ies) — a kernel's contract version moved without "
            "reseeding (python -m paddle_tpu.kernels.autotune --seed)")
    # Non-dryrun: fold this session's measured winners in and persist —
    # THIS is the documented "refresh measured entries on the target
    # device" path (the dryrun CI smoke must not dirty the checkout).
    # Seed-time cost_prior stamps survive the overwrite.
    if not dryrun:
        for key, ent in tuner.entries.items():
            old = committed.entries.get(key, {})
            if "cost_prior" in old and "cost_prior" not in ent:
                ent = {**ent, "cost_prior": old["cost_prior"]}
            committed.entries[key] = ent
        committed.save(kernels.DEFAULT_CACHE_PATH)

    geomean = float(np.exp(np.mean(np.log(np.maximum(speedups, 1e-9)))))
    result = {
        "metric": "kernels_autotune_speedup_geomean",
        "value": round(geomean, 3),
        "unit": "x vs default blocks",
        "vs_baseline": round(geomean, 3),   # 1.0 == defaults already best
        "kernels": per_kernel,
        "impl": impl,
        "tuner_cache_hits": tuner.hits,
        "tuner_cache_misses": tuner.misses,
        "tuner_stale_entries": tuner.stale,
        "committed_cache_entries": len(committed.entries),
        "committed_cache_stale": committed_stale,
        "device": getattr(dev, "device_kind", dev.platform),
        "dryrun": bool(dryrun),
        "_telemetry": {"steps": len(speedups),
                       "dt": time.perf_counter() - t_bench0,
                       "examples_per_step": 1},
    }
    missing = [k for k in KERNELS_SCHEMA if k not in result]
    if missing:
        raise RuntimeError(f"BENCH_KERNELS schema self-check failed: "
                           f"missing {missing}")
    path = kernels_json_path(dryrun)
    with open(path, "w") as f:
        json.dump({k: v for k, v in result.items()
                   if k != "_telemetry"}, f, indent=2)
    result["bench_json"] = path
    return result


_BENCHES = {
    "bert": (run_bench, "bert_base_tokens_per_sec_per_chip",
             "tokens/s/chip"),
    "resnet50": (run_bench_resnet, "resnet50_images_per_sec_per_chip",
                 "images/s/chip"),
    "transformer": (run_bench_transformer,
                    "transformer_big_packed_tokens_per_sec_per_chip",
                    "real tokens/s/chip"),
    "deepfm": (run_bench_deepfm, "deepfm_examples_per_sec_per_chip",
               "examples/s/chip"),
    "serving": (run_bench_serving, "serving_decode_tokens_per_sec",
                "tokens/s"),
    "embedding_serving": (run_bench_embedding_serving,
                          "embedding_serving_examples_per_sec",
                          "examples/s"),
    "router": (run_bench_router, "router_aggregate_tokens_per_sec",
               "tokens/s"),
    "kernels": (run_bench_kernels, "kernels_autotune_speedup_geomean",
                "x vs default blocks"),
    "net_router": (run_bench_net_router, "net_router_tokens_per_sec",
                   "tokens/s"),
    "disagg": (run_bench_disagg, "serving_disagg_ttft_p99_improvement",
               "x vs colocated (mixed burst)"),
    "prefix_fleet": (run_bench_prefix_fleet,
                     "prefix_fleet_prefill_tokens_per_served_token",
                     "prefill tokens/served token (lower is better)"),
}


def main() -> int:
    # --model bert (default) | resnet50 | transformer | deepfm | ...
    # Prints ONE JSON line on success; any exception propagates and the
    # process exits non-zero — a failed bench never looks like a result.
    which = "bert"
    if "--model" in sys.argv:
        which = sys.argv[sys.argv.index("--model") + 1]
    if which not in _BENCHES:
        raise ValueError(f"unknown --model {which!r} "
                         f"(expected {'|'.join(_BENCHES)})")
    from paddle_tpu import observability as obs
    from paddle_tpu.core.compile_cache import enable_compile_cache
    dev = acquire_device()
    enable_compile_cache()
    obs.install_compile_listener()  # compiles_cum covers the warmup
    if which in ("serving", "embedding_serving", "router", "kernels",
                 "net_router", "disagg", "prefix_fleet"):
        # CI smoke: tiny sizes + schema self-check
        result = _BENCHES[which][0](dev, dryrun="--dryrun" in sys.argv)
    else:
        result = _BENCHES[which][0](dev)
    log_path = write_bench_telemetry(result)
    if log_path:
        result["metrics_log"] = log_path
    result.pop("_telemetry", None)  # never leak internals to the JSON line
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
