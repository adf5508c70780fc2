"""AOT startup subsystem tests (mxnet_tpu/aot/).

CPU-deterministic throughout: the persistent compile cache and export
store both work on the CPU PJRT backend, so the restart story — a
second engine start that loads every bucket program instead of tracing
— is assertable in-process by clearing the shared program cache and
counting compile activity through telemetry.  The cold-vs-warm *wall
time* claim lives in tools/startup_bench.py (contract-tested in
test_bench_contract.py's slow tier); here we pin the *semantics*:
zero fresh traces, zero persistent-cache misses, token-identical
output, and silent fallbacks for missing/stale/corrupt artifacts.
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import mxnet_tpu as mx
from mxnet_tpu import aot, telemetry
from mxnet_tpu.serve import engine as engine_mod

VOCAB = 89


# -- shared fixtures ---------------------------------------------------------
@pytest.fixture(autouse=True)
def fresh_program_cache():
    """Engines in this module share one model config; the process-wide
    program cache would otherwise leak compiled programs between tests
    and mask the cold paths under test."""
    engine_mod._STEP_CACHE.clear()
    yield


@pytest.fixture
def tel():
    """Recording telemetry for the duration of one test."""
    telemetry.reset()
    telemetry.enable()
    yield telemetry
    telemetry.disable()
    telemetry.reset()


@pytest.fixture
def compile_cache(tmp_path, monkeypatch):
    """Persistent compile cache in a per-test dir, placed the way a
    deployment places it — ``JAX_COMPILATION_CACHE_DIR`` (jax reads the
    variable at import, so the fixture applies it to the live config
    too); jax config restored afterwards so later tests never write
    into a deleted tmp dir."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    prev_size = jax.config.jax_persistent_cache_min_entry_size_bytes
    cache_dir = str(tmp_path / "cc")
    monkeypatch.setenv(aot.cache.ENV_DIR, cache_dir)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    mgr = aot.cache.CompileCacheManager().enable()
    assert mgr.dir == cache_dir        # adopted verbatim, no sub-directory
    yield mgr
    jax.config.update("jax_compilation_cache_dir", prev)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      prev_min)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                      prev_size)
    # drop the memoized cache object: it still points at this test's
    # (deleted) tmp dir and jax would otherwise keep using it
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def model():
    """Tiny gpt2-style net + params (same recipe as test_serve)."""
    S = 96
    net = mx.models.gpt(VOCAB, S, num_layers=2, d_model=32, num_heads=4)
    arg_shapes, _, _ = net.infer_shape(data=(1, S), softmax_label=(1, S))
    rng = np.random.RandomState(3)
    params = {}
    for name, shp in zip(net.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            continue
        scale = 0.35 if name.endswith("weight") else 0.0
        params[name] = (rng.randn(*shp) * scale
                        + (1.0 if name.endswith("gamma") else 0.0)
                        ).astype(np.float32)
    return net, params


def _engine(model, **kw):
    net, params = model
    kw.setdefault("block_size", 4)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("max_prefills_per_step", 2)
    return mx.serve.Engine(params, symbol=net, **kw)


def _counts(name):
    snap = telemetry.registry().snapshot().get(name, {"samples": []})
    return {tuple(sorted(s["labels"].items())): s["value"]
            for s in snap["samples"]}


def _total(name, **labels):
    return sum(v for k, v in _counts(name).items()
               if all((lk, lv) in k for lk, lv in labels.items()))


def _serve(eng, prompts, max_new=8):
    reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run()
    assert all(r.status == "finished" for r in reqs)
    return [r.tokens for r in reqs]


def _prompts(rng=None):
    rng = rng or np.random.RandomState(7)
    return [rng.randint(0, VOCAB, (n,)).astype(np.int32)
            for n in (7, 12, 5)]


# -- compile-cache manager ---------------------------------------------------
def test_cache_manager_wires_jax_and_counts(tel, compile_cache):
    """Compile-cache wiring: a fresh jit of an already-compiled
    module is served from disk, visible as hit/miss/put counters and
    on-disk entries; the snapshot line is metrics_report-loadable."""
    import jax
    import jax.numpy as jnp

    def build():
        # a FRESH function object per call (same name, same body): the
        # second jit misses every in-process cache but lowers to the
        # identical module, so only the disk cache can satisfy it
        def f(x):
            return jnp.sin(x) @ jnp.cos(x) + jnp.tanh(x)

        return jax.jit(f)

    x = jnp.ones((32, 32), jnp.float32)
    build()(x).block_until_ready()
    misses = _total("mxtpu_compile_cache_misses")
    puts = _total("mxtpu_compile_cache_puts")
    assert misses >= 1 and puts == misses
    st = compile_cache.stats()
    assert st["entries"] >= 1 and st["bytes"] > 0
    build()(x).block_until_ready()
    assert _total("mxtpu_compile_cache_hits") >= 1
    assert _total("mxtpu_compile_cache_misses") == misses

    snap_path = compile_cache.snapshot_to()
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import metrics_report

    metrics, _ = metrics_report.load_jsonl(snap_path)
    assert metrics["mxtpu_compile_cache_dir_entries"]["samples"][0][
        "value"] >= 1
    assert "mxtpu_compile_cache_hits" in metrics


def test_cache_placed_from_outside(monkeypatch):
    """Where the cache lives is decided outside the program: with
    ``JAX_COMPILATION_CACHE_DIR`` set nothing here touches jax's
    directory; unset, the cache is ``<checkout>/.jax_cache`` — and a
    CPU-pinned process (this suite) gets no persistent cache at all."""
    import jax

    assert aot.cache.DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
    monkeypatch.delenv(aot.cache.ENV_DIR, raising=False)
    monkeypatch.setattr(aot.cache, "_active", None)
    assert jax.config.jax_platforms == "cpu"
    assert aot.cache.enable_from_env() is None
    assert aot.cache.active() is None


# -- export store ------------------------------------------------------------
def test_export_store_roundtrip_stale_and_corrupt(tel, tmp_path):
    import jax
    import jax.numpy as jnp

    store = aot.ExportStore(str(tmp_path / "aot"))
    fp = aot.fingerprint(subsystem="t", bucket=4)
    assert store.load(fp) is None                      # missing: silent

    def g(x):
        return jnp.tanh(x @ x)

    spec = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    exported = jax.export.export(jax.jit(g))(spec)
    path = store.save(fp, exported)
    assert path and os.path.exists(path)
    loaded = store.load(fp)
    assert loaded is not None
    x = np.ones((8, 8), np.float32)
    np.testing.assert_allclose(np.asarray(jax.jit(loaded.call)(x)),
                               np.tanh(x @ x), rtol=1e-6)

    # stale: same file name cannot be produced by a different fp, so
    # simulate a collision by rewriting the header in place
    raw = open(path, "rb").read()
    n = int.from_bytes(raw[8:16], "little")
    other = json.dumps({"fingerprint": dict(fp, bucket=8)},
                       sort_keys=True).encode()
    with open(path, "wb") as f:      # same-length header keeps offsets
        f.write(raw[:8] + len(other).to_bytes(8, "little") + other
                + raw[16 + n:])
    assert store.load(fp) is None
    assert _total("mxtpu_aot_errors_total", kind="stale") == 1

    # corrupt: truncated blob deserializes to None, never raises
    store.save(fp, exported)
    with open(path, "wb") as f:
        f.write(open(path, "rb").read()[:40])
    assert store.load(fp) is None
    assert _total("mxtpu_aot_errors_total", kind="corrupt") == 1


# -- warmup manifests --------------------------------------------------------
def test_manifest_recorder_and_loader(tmp_path, monkeypatch):
    path = str(tmp_path / "m.jsonl")
    rec = aot.ManifestRecorder("spec-a", path)
    assert rec.record("prefill", 16) is True
    assert rec.record("prefill", 16) is False          # deduped
    rec.record("decode", 4)
    assert [e["bucket"] for e in rec.entries()] == [16, 4]

    # a second engine's recorder appends to the same file
    aot.ManifestRecorder("spec-b", path).record("decode", 8)
    with open(path, "a") as f:
        f.write("not json\n")                          # torn tail line
    all_entries = aot.load_manifest(path)
    assert len(all_entries) == 3                       # junk skipped
    mine = aot.load_manifest(path, spec_digest="spec-a")
    assert [(e["kind"], e["bucket"]) for e in mine] \
        == [("prefill", 16), ("decode", 4)]            # foreign spec out

    monkeypatch.setenv(aot.warmup.ENV_MANIFEST, path)
    assert len(aot.load_manifest(None)) == 3           # env resolution
    monkeypatch.delenv(aot.warmup.ENV_MANIFEST)
    assert aot.load_manifest(None) == []
    assert aot.load_manifest(str(tmp_path / "absent.jsonl")) == []


def test_engine_records_manifest_to_env_path(tel, tmp_path, monkeypatch,
                                             model):
    path = str(tmp_path / "traffic.jsonl")
    monkeypatch.setenv(aot.warmup.ENV_MANIFEST, path)
    eng = _engine(model)
    _serve(eng, _prompts())
    eng.shutdown()
    on_disk = aot.load_manifest(path)
    assert sorted((e["kind"], e["bucket"]) for e in on_disk) \
        == sorted((e["kind"], e["bucket"]) for e in eng.manifest())
    assert len(on_disk) >= 3

    # warmup() with no argument replays the env manifest — and replay
    # must not re-append what it just read
    size = os.path.getsize(path)
    engine_mod._STEP_CACHE.clear()
    eng2 = _engine(model)
    assert eng2.warmup() == len(on_disk)
    assert os.path.getsize(path) == size
    eng2.shutdown()


# -- the restart story -------------------------------------------------------
def test_engine_cold_warm_restart_zero_fresh_traces(tel, compile_cache,
                                                    tmp_path, model):
    """The acceptance gate: build an engine, capture its manifest, tear
    everything down (shared program cache included), and assert the
    second construction + warmup() traces NOTHING — every program loads
    from the export store, every XLA compile hits the persistent cache
    — while decoding token-identical output."""
    aot_dir = str(tmp_path / "aot")
    prompts = _prompts()

    cold = _engine(model, aot_dir=aot_dir)
    toks_cold = _serve(cold, prompts)
    manifest = cold.manifest()
    cold.shutdown()
    assert _total("mxtpu_aot_programs_total", source="trace") >= 5
    assert aot.ExportStore(aot_dir).entries()

    engine_mod._STEP_CACHE.clear()                     # simulated restart
    traces = _total("mxtpu_aot_programs_total", source="trace")
    cache_misses = _total("mxtpu_compile_cache_misses")

    warm = _engine(model, aot_dir=aot_dir)
    warmed = warm.warmup(manifest)
    assert warmed == len(manifest)
    # engine ready with ZERO fresh compile work:
    assert _total("mxtpu_aot_programs_total", source="trace") == traces
    assert _total("mxtpu_aot_programs_total", source="artifact") == warmed
    assert _total("mxtpu_compile_cache_misses") == cache_misses
    assert _total("mxtpu_compile_cache_hits") >= warmed

    toks_warm = _serve(warm, prompts)
    assert toks_warm == toks_cold
    # serving after warmup compiled nothing new either
    assert _total("mxtpu_aot_programs_total", source="trace") == traces
    warm.shutdown()


def test_engine_corrupt_and_stale_artifacts_fall_back(tel, tmp_path,
                                                      model):
    """Mangled artifacts must cost a fresh trace, never correctness."""
    aot_dir = str(tmp_path / "aot")
    prompts = _prompts()
    cold = _engine(model, aot_dir=aot_dir)
    toks_cold = _serve(cold, prompts)
    cold.shutdown()

    store = aot.ExportStore(aot_dir)
    for path, _ in store.entries():
        raw = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(raw[: len(raw) // 2])              # torn mid-blob

    engine_mod._STEP_CACHE.clear()
    traces = _total("mxtpu_aot_programs_total", source="trace")
    eng = _engine(model, aot_dir=aot_dir)
    toks = _serve(eng, prompts)
    assert toks == toks_cold
    assert _total("mxtpu_aot_programs_total", source="trace") > traces
    assert _total("mxtpu_aot_errors_total", kind="corrupt") >= 1
    eng.shutdown()

    # stale config: a differently-configured engine must ignore the
    # (freshly rewritten) artifacts — fingerprint mismatch, fresh trace
    engine_mod._STEP_CACHE.clear()
    loads = _total("mxtpu_aot_programs_total", source="artifact")
    other = _engine(model, aot_dir=aot_dir, num_blocks=48)
    _serve(other, prompts)
    assert _total("mxtpu_aot_programs_total", source="artifact") == loads
    other.shutdown()


def test_engine_warmup_grid_and_range_checks(tel, model):
    """warmup(None) with no manifest warms the full bucket grid;
    out-of-range or unknown entries are skipped, not compiled."""
    eng = _engine(model, max_batch=2, max_model_len=16)
    n = eng.warmup()
    # decode {1,2} + prefill {1,2,4,8,16} + chunk {1,2,4,8,16} (the
    # suffix/chunk program family prefix-cache hits and chunked
    # prefills run; its cap clamps to max_model_len here)
    assert n == 12
    assert eng.warmup([{"kind": "decode", "bucket": 99},
                       {"kind": "prefill", "bucket": 1000},
                       {"kind": "chunk", "bucket": 1000},
                       {"kind": "mystery", "bucket": 2},
                       {"kind": "decode", "bucket": 2}]) == 1
    eng.shutdown()
    # non-power-of-two caps are real clamp buckets live traffic hits —
    # the grid must include them (decode {1,2,3} + prefill {1..16,24}
    # + chunk {1..16,24})
    engine_mod._STEP_CACHE.clear()
    eng2 = _engine(model, max_batch=3, max_model_len=24)
    assert eng2.warmup() == 15
    eng2.shutdown()


def test_engine_warmup_raises_on_compile_failure(tel, model, monkeypatch):
    """A program that cannot be built or compiled is not "ready":
    warmup() raises instead of deferring the failure to the first
    request that needs the program."""
    eng = _engine(model)

    def broken(kind, bucket):
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setattr(eng, "_program_builder", broken)
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        eng.warmup([{"kind": "decode", "bucket": 2}])
    monkeypatch.undo()

    def uncompilable(kind, bucket):
        import jax

        def f(*args):
            raise ValueError("compile-time failure")

        return jax.jit(f)

    monkeypatch.setattr(eng, "_program_builder", uncompilable)
    with pytest.raises(ValueError, match="compile-time failure"):
        eng.warmup([{"kind": "decode", "bucket": 2}])
    eng.shutdown()


def test_engine_warmup_precompiles_without_aot_store(tel, model):
    """warmup() must mean 'compiled', not 'will compile at the first
    unlucky request' — even with no export store or compile cache
    configured.  After a full-grid warmup, serving triggers zero
    backend compiles."""
    ev = "/jax/core/compile/backend_compile_duration"
    pre = _engine(model, max_batch=2, max_model_len=32)
    _serve(pre, _prompts())            # warm process-level jits
    pre.shutdown()
    engine_mod._STEP_CACHE.clear()

    eng = _engine(model, max_batch=2, max_model_len=32)
    eng.warmup()
    before = _total("mxtpu_jax_events_total", event=ev)
    assert before > 0                  # warmup itself really compiled
    _serve(eng, _prompts())
    assert _total("mxtpu_jax_events_total", event=ev) == before
    eng.shutdown()


# -- fused train step --------------------------------------------------------
def test_fused_step_aot_roundtrip(tel, compile_cache, tmp_path,
                                  monkeypatch):
    """The fused train program exports on first use and a 'restarted'
    module loads it instead of re-tracing — with identical weights."""
    monkeypatch.setenv(aot.export_store.ENV_DIR, str(tmp_path / "aot"))

    def fit_once():
        rng = np.random.RandomState(0)
        X = rng.randn(64, 8).astype(np.float32)
        y = rng.randint(0, 4, 64).astype(np.float32)
        it = mx.io.NDArrayIter(X, y, batch_size=16)
        # explicit layer name: the auto-naming counter is process-global
        # and would change the symbol JSON (and so the AOT fingerprint)
        # between the two "processes" this test simulates
        net = mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                                  name="fc"),
            name="softmax")
        mx.random.seed(0)
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.fit(it, num_epoch=2, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1},
                initializer=mx.initializer.Xavier(), kvstore=None)
        return mod.get_params()[0]

    p1 = fit_once()
    saves = _total("mxtpu_aot_saves_total", kind="fused-step")
    assert saves == 1
    p2 = fit_once()                                    # "restart"
    assert _total("mxtpu_aot_loads_total", kind="fused-step") == 1
    assert _total("mxtpu_aot_saves_total", kind="fused-step") == saves
    for k in p1:
        np.testing.assert_allclose(p1[k].asnumpy(), p2[k].asnumpy(),
                                   rtol=1e-6, atol=1e-7)
