"""Unified telemetry (ISSUE 10): metric registry, span tracing, flight
recorder and tail-latency histograms across the FTaaS stack.

Acceptance invariants:
- generated tokens are bit-identical telemetry-on vs. telemetry-off, on an
  attention plan (chunked + paged) and an ssm plan (chunked) — telemetry only
  reads host-side values and never touches a jitted computation;
- legacy counters stay exact when mirrored into the registry, and agree
  across engine modes (chunked+paged+burst vs. the batched baseline) for
  everything that counts tokens/requests (tick counts legitimately differ);
- exported traces are valid Chrome-trace-event JSON (schema + per-lane span
  nesting), loadable in Perfetto and parsed by ``repro.trace_summary``;
- the disabled path is zero-cost by construction: shared null context /
  null metric singletons, no tracer, no recorder;
- one span helper (``telemetry.span``) writes a profiler annotation when
  annotations are armed and a Chrome-trace event when a tracer is attached,
  and every engine tick's host work runs in its phase spans.

The chaos-side acceptance (quarantine postmortem with failing seq ids) lives
in tests/test_faults.py next to the rest of the chaos suite.
"""
import json
import os
import time

import jax
import numpy as np
import pytest

from repro import telemetry
from repro.configs import registry
from repro.configs.base import ColaConfig
from repro.core import gl
from repro.core.session import ColaSession
from repro.data.pipeline import SyntheticLM
from repro.models import model as M
from repro.optim import optimizers as opt
from repro.runtime.serve_loop import Request, ServeEngine
from repro.runtime.train_loop import TrainLoop
from repro.telemetry import NULL_CONTEXT, Telemetry, span, validate_trace
from repro.telemetry.metrics import (NULL_METRIC, Histogram, MetricRegistry,
                                     percentiles)
from repro.telemetry.recorder import FlightRecorder
from repro.telemetry.tracing import Tracer


def _tiny(name="smollm-135m", **over):
    cfg = registry.reduced_config(name)
    base = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
                d_ff=128, vocab_size=128)
    base.update(over)
    return cfg.replace(**{k: v for k, v in base.items() if hasattr(cfg, k)})


@pytest.fixture(autouse=True)
def _disarmed():
    """Each test starts and ends with profiler annotations disarmed (the
    switch is process-wide, and a traced benchmark run arms it)."""
    telemetry.enable_jax_annotations(False)
    yield
    telemetry.enable_jax_annotations(False)


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=p) for p in lens]


# one attention plan (chunked + paged, multi-user banks) and one ssm plan
# (chunked, bankless — qv taps don't exist on the ssm backbone): the two
# cache disciplines the bit-identity guarantee must cover
SERVE_CASES = {
    "smollm-135m": dict(over={}, users=2,
                        kw=dict(prefill_chunk=4, kv_layout="paged",
                                kv_block=8)),
    "mamba2-370m": dict(over=dict(ssm_headdim=16, ssm_state=16), users=0,
                        kw=dict(prefill_chunk=4)),
}


def _serve(name, telemetry=None, lens=(5, 11, 7, 4), max_new=6, slots=2,
           **extra_kw):
    case = SERVE_CASES[name]
    cfg = _tiny(name, **case["over"])
    key = jax.random.PRNGKey(0)
    params = M.init(cfg, key)
    n_users = case["users"]
    banks = None
    if n_users:
        cc = ColaConfig(mode="lora", family="lowrank", taps="qv", rank=4)
        banks = [gl.init_adapters(cfg, cc, jax.random.fold_in(key, u))
                 for u in range(n_users)]
    kw = dict(case["kw"])
    kw.update(extra_kw)
    eng = ServeEngine(cfg, params, slots=slots, max_len=32,
                      user_adapters=banks, telemetry=telemetry, **kw)
    reqs = [Request(rid=i, user=i % max(n_users, 1), prompt=p,
                    max_new=max_new)
            for i, p in enumerate(_prompts(cfg, lens))]
    for r in reqs:
        eng.submit(r)
    eng.run_until_idle()
    return eng, [r.out for r in reqs]


# ---------------------------------------------------------------------------
# metric registry units
# ---------------------------------------------------------------------------

def test_percentiles_helper():
    assert percentiles([]) is None
    p = percentiles([1.0, 2.0, 3.0, 4.0])
    assert p["count"] == 4 and p["max"] == 4.0 and p["mean"] == 2.5
    assert p["p50"] == 2.5 and p["p99"] <= 4.0


def test_histogram_exact_then_interpolated():
    h = Histogram(buckets=(1.0, 2.0, 4.0, 8.0), sample_cap=8)
    for v in (0.5, 1.5, 3.0, 7.0):
        h.observe(v)
    # ring still complete: percentiles are exact
    assert h.percentile(50) == pytest.approx(np.percentile([0.5, 1.5, 3.0, 7.0], 50))
    s = h.summary()
    assert s["count"] == 4 and s["min"] == 0.5 and s["max"] == 7.0
    # overflow the ring: interpolation stays within the observed range and
    # monotone in q
    for _ in range(100):
        h.observe(3.0)
    q = [h.percentile(x) for x in (10, 50, 90, 99)]
    assert all(0.0 <= v <= h.max for v in q)
    assert q == sorted(q)
    # beyond the last bound lands in +Inf, never lost
    h.observe(100.0)
    assert h.counts.sum() == h.count


def test_registry_absorb_mirrors_stat_dicts():
    reg = MetricRegistry()
    reg.absorb("serve", {"ticks": 7, "decode_time": 0.5, "ok": True,
                         "label": "skipped", "missing": None,
                         "store": {"hits": 3}})
    snap = reg.snapshot()
    assert snap["serve.ticks"] == 7
    assert snap["serve.decode_time"] == 0.5
    assert snap["serve.ok"] == 1
    assert snap["serve.store.hits"] == 3
    assert "serve.label" not in snap and "serve.missing" not in snap
    # re-absorb keeps the source authoritative (set, not inc)
    reg.absorb("serve", {"ticks": 9})
    assert reg.snapshot()["serve.ticks"] == 9


def test_registry_disabled_is_null():
    reg = MetricRegistry(enabled=False)
    assert reg.counter("a") is NULL_METRIC
    assert reg.gauge("b") is NULL_METRIC
    assert reg.histogram("c") is NULL_METRIC
    reg.absorb("x", {"n": 1})
    assert reg.snapshot() == {}
    reg.emit(step=0)            # no stream, no crash


def test_registry_emit_jsonl(tmp_path):
    reg = MetricRegistry()
    path = str(tmp_path / "telemetry.jsonl")
    reg.stream_to(path)
    reg.counter("train.step").set(3)
    reg.histogram("train.step_s").observe(0.01)
    reg.emit(step=3)
    reg.emit(step=4)
    recs = [json.loads(l) for l in open(path)]
    assert len(recs) == 2
    assert recs[0]["step"] == 3 and recs[0]["metrics"]["train.step"] == 3
    assert recs[1]["metrics"]["train.step_s"]["count"] == 1


def test_prometheus_export():
    reg = MetricRegistry()
    reg.counter("serve.ticks").set(5)
    reg.gauge("serve.decode_time").set(1.5)
    h = reg.histogram("serve.ttft_s", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.to_prometheus()
    assert "# TYPE serve_ticks counter\nserve_ticks 5" in text
    assert "serve_decode_time 1.5" in text
    # cumulative buckets: 1 under 0.1, 2 under 1.0, 3 total
    assert 'serve_ttft_s_bucket{le="0.1"} 1' in text
    assert 'serve_ttft_s_bucket{le="1"} 2' in text
    assert 'serve_ttft_s_bucket{le="+Inf"} 3' in text
    assert "serve_ttft_s_count 3" in text


# ---------------------------------------------------------------------------
# tracer + schema validation units
# ---------------------------------------------------------------------------

def test_tracer_spans_nest_and_validate(tmp_path):
    tr = Tracer()
    tr.name_thread(0, "serve")
    with tr.span("outer", tid=0, tick=1):
        with tr.span("inner", tid=0):
            pass
    with tr.span("offload", cat="offload", tid=1, seq=7):
        pass
    doc = tr.to_doc()
    assert validate_trace(doc) == []
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert [s["name"] for s in spans] == ["inner", "outer", "offload"]
    assert spans[1]["args"] == {"tick": 1}
    # spans carry the ids downstream tooling joins on
    assert spans[2]["args"]["seq"] == 7
    path = tr.export(str(tmp_path / "t.json"))
    assert validate_trace(json.load(open(path))) == []


def test_validate_trace_rejects_malformed():
    assert validate_trace({}) != []
    assert validate_trace({"traceEvents": []}) != []
    # missing required fields
    assert validate_trace({"traceEvents": [{"name": "x"}]}) != []
    # negative duration
    bad = {"traceEvents": [{"name": "x", "ph": "X", "pid": 1, "tid": 0,
                            "ts": 0.0, "dur": -1.0}]}
    assert any("dur" in p for p in validate_trace(bad))
    # overlapping (non-nested) spans on one lane
    overlap = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 0, "ts": 0.0, "dur": 10.0},
        {"name": "b", "ph": "X", "pid": 1, "tid": 0, "ts": 5.0, "dur": 10.0},
    ]}
    assert any("overlaps" in p for p in validate_trace(overlap))
    # same shape on separate lanes is fine
    two_lanes = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 0, "ts": 0.0, "dur": 10.0},
        {"name": "b", "ph": "X", "pid": 1, "tid": 1, "ts": 5.0, "dur": 10.0},
    ]}
    assert validate_trace(two_lanes) == []


# ---------------------------------------------------------------------------
# flight recorder units
# ---------------------------------------------------------------------------

def test_recorder_ring_bounded_and_postmortem(tmp_path):
    rec = FlightRecorder(capacity=4, out_dir=str(tmp_path))
    for i in range(10):
        rec.record("user", 1, "push", seq=i)
    rec.record("slot", 0, "admit", rid=3)
    assert rec.keys() == [("slot", 0), ("user", 1)]
    evs = rec.events("user", 1)
    assert len(evs) == 4 and [e["seq"] for e in evs] == [6, 7, 8, 9]
    pm = rec.dump("user", 1, "quarantined after 2 failed fit rounds")
    assert pm["reason"].startswith("quarantined")
    assert [e["seq"] for e in pm["events"]] == [6, 7, 8, 9]
    assert os.path.exists(pm["path"])
    on_disk = json.load(open(pm["path"]))
    assert on_disk["events"][-1]["seq"] == 9
    # dumping an unknown key is an empty postmortem, not a crash
    assert rec.dump("slot", 99, "no such ring")["events"] == []


def test_recorder_capacity_validated():
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


# ---------------------------------------------------------------------------
# zero-cost-when-disabled: identity, not timing
# ---------------------------------------------------------------------------

def test_disabled_paths_share_null_singletons():
    tm_off = Telemetry(enabled=False)
    assert not tm_off and tm_off.tracer is None and tm_off.recorder is None
    assert tm_off.span("x") is NULL_CONTEXT
    assert tm_off.registry.counter("a") is NULL_METRIC
    assert tm_off.snapshot() == {}
    assert tm_off.export_trace("/nonexistent/never-written") is None
    tm_off.record("user", 0, "kind")
    assert tm_off.dump("user", 0, "r") is None
    # Telemetry(enabled=False) and telemetry=None are indistinguishable
    cfg = _tiny()
    eng_none = ServeEngine(cfg, M.init(cfg, jax.random.PRNGKey(0)), slots=2,
                           max_len=32)
    assert eng_none.tm is None and eng_none._tracer is None
    # the one span helper, disarmed and without a tracer
    assert span("serve.tick", eng_none._tracer, tick=0) is NULL_CONTEXT
    assert span("offload.fit") is NULL_CONTEXT
    assert eng_none._h_ttft is NULL_METRIC
    assert eng_none.telemetry_snapshot() == {}
    # enabled-without-trace still has no tracer: spans stay free
    tm_plain = Telemetry()
    assert tm_plain and tm_plain.span("x") is NULL_CONTEXT


@pytest.mark.parametrize("site", ["facade", "helper"])
def test_disabled_span_overhead_bounded(site):
    """100k disabled span entries must be pure-python cheap (no allocation,
    no syscalls) — an absolute wall bound, generous enough for shared CI."""
    open_span = (Telemetry(enabled=False).span if site == "facade"
                 else lambda name: span(name, None))
    t0 = time.perf_counter()
    for _ in range(100_000):
        with open_span("serve.tick"):
            pass
    assert time.perf_counter() - t0 < 2.0


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records entries."""
    opened: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.opened.append(self.name)

    def __exit__(self, *exc):
        return False


@pytest.mark.parametrize("annotations", [False, True], ids=["off", "armed"])
@pytest.mark.parametrize("traced", [False, True], ids=["no_tracer", "tracer"])
def test_span_writes_to_what_is_on(monkeypatch, annotations, traced):
    """One call site: a profiler annotation when armed, a Chrome-trace event
    when a tracer is attached, both when both, the null context when
    neither."""
    monkeypatch.setattr(telemetry, "_ANNOTATION",
                        _FakeAnnotation if annotations else None)
    monkeypatch.setattr(_FakeAnnotation, "opened", [])
    tracer = Tracer() if traced else None
    ctx = span("serve.prep", tracer, tick=3)
    if not (annotations or traced):
        assert ctx is NULL_CONTEXT
    with ctx:
        pass
    assert _FakeAnnotation.opened == (["serve.prep"] if annotations else [])
    if traced:
        (ev,) = tracer.events
        assert ev["name"] == "serve.prep" and ev["args"] == {"tick": 3}


def test_enable_jax_annotations_arms_the_profiler_annotation():
    from jax.profiler import TraceAnnotation
    telemetry.enable_jax_annotations(True)
    assert isinstance(span("serve.tick"), TraceAnnotation)
    telemetry.enable_jax_annotations(False)
    assert span("serve.tick") is NULL_CONTEXT


# ---------------------------------------------------------------------------
# serve engine: bit-identity, counter consistency, tail latency
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("annotations", [False, True], ids=["off", "armed"])
@pytest.mark.parametrize("name", sorted(SERVE_CASES))
def test_tokens_bit_identical_telemetry_on_off(name, annotations, tmp_path):
    _, ref_outs = _serve(name, telemetry=None)
    tm = Telemetry(trace=True, out_dir=str(tmp_path))
    telemetry.enable_jax_annotations(annotations)
    eng, outs = _serve(name, telemetry=tm)
    assert outs == ref_outs, "telemetry must never perturb generated tokens"
    if annotations:
        # annotations alone, no Telemetry attached (the benchmark's case)
        assert _serve(name, telemetry=None)[1] == ref_outs
    # and the instrumented run actually observed the work
    snap = eng.telemetry_snapshot()
    assert snap["serve.completed"] == len(ref_outs)
    assert snap["serve.ttft_s"]["count"] == len(ref_outs)
    assert validate_trace(tm.tracer.to_doc()) == []


def test_counters_agree_across_engine_modes():
    """Token/request counters must agree between the batched baseline and the
    chunked+paged+burst engine on the same workload — tick/dispatch counters
    (ticks, prefill_calls, chunk_rounds) legitimately differ."""
    base_eng, base_outs = _serve("smollm-135m", prefill_chunk=None,
                                 kv_layout="dense",
                                 telemetry=Telemetry())
    burst_eng, burst_outs = _serve("smollm-135m", decode_burst=4,
                                   telemetry=Telemetry())
    assert base_outs == burst_outs
    a, b = base_eng.telemetry_snapshot(), burst_eng.telemetry_snapshot()
    for key in ("serve.tokens", "serve.decode_tokens", "serve.prefill_tokens",
                "serve.completed", "serve.admitted", "serve.rejected"):
        assert a[key] == b[key], f"{key}: {a[key]} != {b[key]}"
    # the registry mirrors the legacy dict exactly — same authority
    assert a["serve.tokens"] == base_eng.stats["tokens"]
    assert b["serve.decode_tokens"] == burst_eng.stats["decode_tokens"]
    # paged engine exposes pager.* next to serve.*
    assert b["pager.allocs"] == burst_eng.pager.stats["allocs"]
    burst_eng.pager.assert_empty()


def test_throughput_percentiles_always_on():
    """Tail percentiles in throughput() ride the always-on rings: present
    without telemetry, shaped {count, mean, max, p50, p95, p99}."""
    eng, outs = _serve("smollm-135m", telemetry=None)
    tp = eng.throughput()
    for key in ("ttft", "latency", "decode_tick", "prefill"):
        p = tp[key]
        assert p is not None and p["count"] > 0
        assert set(p) == {"count", "mean", "max", "p50", "p95", "p99"}
        assert p["p50"] <= p["p95"] <= p["p99"] <= p["max"]
    assert tp["ttft"]["count"] == len(outs)
    assert tp["mean_ttft"] == pytest.approx(tp["ttft"]["mean"])


def test_serve_trace_schema_and_summary(tmp_path):
    """Tier-1 trace schema acceptance: a chunked+paged run exports valid
    Chrome-trace JSON with the serve-span vocabulary, and the
    ``repro.trace_summary`` CLI parses both artifacts."""
    from repro import trace_summary

    tm = Telemetry(trace=True, out_dir=str(tmp_path))
    eng, _ = _serve("smollm-135m", telemetry=tm)
    doc = tm.tracer.to_doc()
    assert validate_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"serve.tick", "serve.admit", "serve.prefill_chunk",
            "serve.decode"} <= names
    # every decode span records its live-slot count and burst width
    decodes = [e for e in doc["traceEvents"]
               if e.get("ph") == "X" and e["name"] == "serve.decode"]
    assert decodes and all(
        e["args"]["live"] >= 1 and e["args"]["burst"] >= 1 for e in decodes)
    # the lane is named for the viewer
    assert any(e["ph"] == "M" and e["args"]["name"] == "serve"
               for e in doc["traceEvents"])

    trace_path = tm.export_trace(str(tmp_path / "serve_trace.json"))
    snap_path = str(tmp_path / "serve_metrics.json")
    with open(snap_path, "w") as f:
        json.dump(eng.telemetry_snapshot(), f)
    assert trace_summary.main([trace_path, "--metrics", snap_path]) == 0
    table = trace_summary.span_table(json.load(open(trace_path)))
    assert any(row["name"] == "serve.tick" for row in table)


PHASES = ("serve.prep", "serve.dispatch", "serve.sync", "serve.emit")


@pytest.mark.parametrize("name,kw", [
    ("smollm-135m", {}),
    ("smollm-135m", dict(decode_burst=4)),
    ("smollm-135m", dict(prefill_chunk=None, kv_layout="dense")),
    ("smollm-135m", dict(prefill_chunk=None, kv_layout="dense",
                         prefill_mode="reference")),
    ("mamba2-370m", {}),
], ids=["chunked_paged", "burst", "unchunked", "reference", "ssm"])
def test_tick_phases_nest_in_ticks(name, kw):
    """Each program run of a tick shows as prep, dispatch and sync, once
    each, and an emit, inside ``serve.tick``; admission runs in
    ``serve.admit``."""
    tm = Telemetry(trace=True)
    eng, _ = _serve(name, telemetry=tm, **kw)
    doc = tm.tracer.to_doc()
    assert validate_trace(doc) == []
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    ticks = [(e["ts"], e["ts"] + e["dur"]) for e in spans
             if e["name"] == "serve.tick"]
    count = {n: 0 for n in PHASES + ("serve.admit",)}
    for e in spans:
        if e["name"] in count:
            count[e["name"]] += 1
            assert any(a <= e["ts"] and e["ts"] + e["dur"] <= b
                       for a, b in ticks), f"{e['name']} outside a tick"
    runs = count["serve.prep"]
    assert runs and count["serve.dispatch"] == count["serve.sync"] == runs
    assert count["serve.admit"] >= 1
    # the reference path feeds a prompt token by token and emits once, at
    # the prompt's last token; every other run emits what it made
    assert count["serve.emit"] >= (1 if eng.prefill_mode == "reference"
                                   else runs)
    decodes = sum(e["name"] == "serve.decode" for e in spans)
    if eng._recurrent:
        # one run per chunk group: a round groups its rows by chunk width
        assert (decodes + eng.stats["chunk_rounds"] <= runs
                <= decodes + eng.stats["prefill_chunks"])
    elif eng.prefill_mode == "batched":
        # one run per decode round and per chunk round or admission prefill
        assert runs == decodes + eng.stats["chunk_rounds"] + (
            0 if eng.prefill_chunk else eng.stats["prefill_calls"])


def test_flight_recorder_scopes_serve(tmp_path):
    tm = Telemetry(out_dir=str(tmp_path))
    eng, _ = _serve("smollm-135m", telemetry=tm)
    keys = tm.recorder.keys()
    # per-slot rings for the serve path, per-user rings for bank installs
    assert any(s == "slot" for s, _ in keys)
    slot_kinds = {e["kind"] for s, k in keys if s == "slot"
                  for e in tm.recorder.events(s, k)}
    assert {"admit", "first_token", "retire"} <= slot_kinds
    # a clean run dumps no postmortems
    assert tm.recorder.postmortems == []


# ---------------------------------------------------------------------------
# train loop: metrics.jsonl + telemetry.jsonl satellites
# ---------------------------------------------------------------------------

def test_trainloop_records_watchdog_and_channel_health(tmp_path):
    cfg = _tiny()
    key = jax.random.PRNGKey(0)
    params = M.init(cfg, key)
    cc = ColaConfig(mode="faithful_offload", family="lowrank", taps="qv",
                    rank=4, merged=True)
    tm = Telemetry(out_dir=str(tmp_path))
    sess = ColaSession(cfg, cc, params, key, optimizer=opt.sgd(0.05),
                       telemetry=tm)
    data = SyntheticLM(cfg, batch=2, seq=16, seed=3)
    loop = TrainLoop(sess, data, str(tmp_path / "run"), log_every=2,
                     telemetry=tm)
    out = loop.run(4, resume=False)

    recs = [json.loads(l)
            for l in open(str(tmp_path / "run" / "metrics.jsonl"))]
    assert recs, "metrics.jsonl must have records"
    for rec in recs:
        wd = rec["watchdog"]
        assert wd["steps"] >= 1 and "median_s" in wd and "p95_s" in wd
        ch = rec["channel_health"]["0"] if "0" in rec["channel_health"] \
            else rec["channel_health"][0]
        assert ch["version"] >= 0 and not ch["quarantined"]
        assert "last_error" in ch and "last_error_seq" in ch
    # run summary carries the watchdog tail stats
    assert out["watchdog"]["steps"] == 4
    assert out["watchdog"]["step_s"]["count"] == 4

    # the registry streamed one snapshot per log point with train.* and
    # channel.* namespaces
    t_recs = [json.loads(l)
              for l in open(str(tmp_path / "run" / "telemetry.jsonl"))]
    assert t_recs
    m = t_recs[-1]["metrics"]
    assert m["train.step"] == 3 and m["train.watchdog.steps"] == 4
    assert m["train.step_s"]["count"] == 4
    assert m["channel.u0.version"] == 4 and m["channel.u0.quarantined"] == 0
