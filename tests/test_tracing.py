"""Host spans and device phase scopes of the join engine, read back from a
real profiler trace and from the compiled step's HLO text, on 1 and on 4
CPU devices."""
import glob
import os
import re

import jax
import pytest
from jax.profiler import ProfileData
from jax.sharding import AxisType

from repro.core import two_way
from repro.core.executor import ExecutorConfig, RetryPolicy
from repro.core.plan import running_example
from repro.data import skewed_join_dataset
from repro.serve import JoinServingEngine

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs 4 virtual devices")

# (tenant, rows per relation, data seed).  Tenant a's third request falls in
# the next shape bucket: a session miss after the tenant's first request.
REQUESTS = [("a", 300, 1), ("b", 300, 4), ("a", 400, 2), ("b", 300, 5),
            ("a", 600, 3)]
QUERIES = {"a": two_way(), "b": running_example()}
PHASE_SPANS = ("join.ingest", "join.dispatch", "join.wait", "join.egress")
KERNEL = re.compile(r"scatter_pack|join_probe|expand_rows")
OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?(\S+)\s*=.*?op_name="([^"]*)"')


def _spans(trace_dir: str) -> list[tuple[str, float, float, dict]]:
    """(name, start ns, end ns, stats) of every `join.*` host span."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("join."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                {k: v for k, v in ev.stats}))
    return out


@pytest.fixture(scope="module", params=[1, 4], ids=["1dev", "4dev"])
def served(request, tmp_path_factory):
    """Two tenants' small joins served under the profiler, with shuffle and
    output capacities forced too small so that requests retry."""
    n_dev = request.param
    mesh = jax.make_mesh((n_dev,), ("cells",), devices=jax.devices()[:n_dev],
                         axis_types=(AxisType.Auto,))
    eng = JoinServingEngine(
        mesh, k=4, config=ExecutorConfig(capacity_factor=0.3, out_capacity=64),
        retry=RetryPolicy(max_retries=12))
    trace_dir = str(tmp_path_factory.mktemp(f"trace{n_dev}"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        reqs = [eng.submit(t, QUERIES[t],
                           skewed_join_dataset(QUERIES[t], n, 800, seed=s))
                for t, n, s in REQUESTS]
        first_round = eng.step_round()
        eng.run()
    finally:
        jax.profiler.stop_trace()
    hlo = [ses.executor._compiled_step(ses._shapes, ses.caps, ses.cap_out)
           .lower(ses._ptable_dev, *ses._device_args).compile().as_text()
           for ses in eng.cache._sessions.values()]
    return {"n_dev": n_dev, "engine": eng, "reqs": reqs,
            "first_round": first_round, "spans": _spans(trace_dir),
            "hlo": hlo}


def _by_rid(spans, name):
    out = {}
    for nm, t0, t1, st in spans:
        if nm == name:
            out.setdefault(st.get("rid"), []).append((t0, t1))
    return out


def test_every_request_has_one_request_span_with_its_rid(served):
    reqs, spans = served["reqs"], served["spans"]
    assert all(r.done for r in reqs)
    rids = sorted(r.rid for r in reqs)
    requests = _by_rid(spans, "join.request")
    assert sorted(requests) == rids
    assert all(len(v) == 1 for v in requests.values())
    submits = _by_rid(spans, "join.submit")
    assert sorted(submits) == rids
    # Every span of the engine carries a request id.
    assert all("rid" in st for _, _, _, st in spans)


@pytest.mark.parametrize("name", PHASE_SPANS)
def test_phase_spans_nest_inside_their_request(served, name):
    requests = _by_rid(served["spans"], "join.request")
    inner = _by_rid(served["spans"], name)
    assert sorted(inner) == sorted(requests)
    for rid, ivs in inner.items():
        (r0, r1), = requests[rid]
        assert all(r0 <= t0 and t1 <= r1 for t0, t1 in ivs), (name, rid)


def test_plan_and_prepare_only_on_first_request_or_session_miss(served):
    rids = {i: r.rid for i, r in enumerate(served["reqs"])}
    plans = _by_rid(served["spans"], "join.plan")
    prepares = _by_rid(served["spans"], "join.prepare")
    # Each tenant plans once, on its first request.
    assert sorted(plans) == sorted([rids[0], rids[1]])
    # Sessions are prepared on each tenant's first request and on a's
    # request in the larger bucket; the other requests hit the cache.
    assert sorted(prepares) == sorted([rids[0], rids[1], rids[4]])
    assert served["engine"].cache.stats["misses"] == 3
    requests = _by_rid(served["spans"], "join.request")
    for rid, ivs in {**plans, **prepares}.items():
        (r0, r1), = requests[rid]
        assert all(r0 <= t0 and t1 <= r1 for t0, t1 in ivs)


def test_retry_spans_count_the_sessions_retries(served):
    eng, spans = served["engine"], served["spans"]
    retries = sum(ts["retries"] for ts in eng.stats["tenants"].values())
    assert retries > 0
    assert sum(nm == "join.retry" for nm, *_ in spans) == retries
    # Each attempt dispatches once and waits once; a retry's re-run nests
    # its ingest, dispatch and wait inside the retry span.
    attempts = sum(ts["batches"] for ts in eng.stats["tenants"].values())
    for name in ("join.dispatch", "join.wait", "join.ingest"):
        assert sum(nm == name for nm, *_ in spans) == attempts
    retry = _by_rid(spans, "join.retry")
    for name in ("join.ingest", "join.dispatch", "join.wait"):
        for rid, ivs in retry.items():
            inside = [iv for iv in _by_rid(spans, name)[rid]
                      if any(r0 <= iv[0] and iv[1] <= r1 for r0, r1 in ivs)]
            assert len(inside) == len(ivs), (name, rid)


def test_request_stamps_are_ordered(served):
    reqs = served["reqs"]
    for r in reqs:
        assert r.submitted_at <= r.started_at <= r.finished_at
    # One round serves each picked tenant in turn: the second starts after
    # the first has finished.
    assert served["first_round"] == 2
    first = sorted(reqs[:2], key=lambda r: r.started_at)
    assert first[0].finished_at <= first[1].started_at


def test_step_kernels_run_under_phase_scopes(served):
    ops = [m.groups() for text in served["hlo"] for line in text.splitlines()
           for m in [OP_NAME.match(line)] if m]
    kernel_ops = [path for _, path in ops if KERNEL.search(path)]
    assert kernel_ops
    for path in kernel_ops:
        assert "phase_map" in path or "phase_reduce" in path, path
    assert any("phase_map" in p for _, p in ops)
    assert any("phase_reduce" in p for _, p in ops)
    a2a = [path for name, path in ops if name.startswith("all-to-all")]
    if served["n_dev"] == 4:
        # One all-to-all per relation, each under the exchange scope; on
        # one device the compiler removes it.
        assert a2a and all("phase_exchange" in p for p in a2a)
    else:
        assert not a2a


def test_scope_names_share_no_prefix_with_a_function_or_primitive(served):
    segs = {re.sub(r"^\w+\((.*)\)$", r"\1", seg)
            for text in served["hlo"]
            for path in re.findall(r'op_name="([^"]*)"', text)
            for seg in path.split("/")}
    scopes = {"phase_map", "phase_exchange", "phase_reduce"}
    assert scopes <= segs
    assert not [s for s in segs - scopes if s.startswith("phase")]


def test_chunked_shuffle_keeps_its_phases_scoped():
    """With `overlap_shuffle` chunks, every pack runs under `phase_map` and
    every chunk's all-to-all under `phase_exchange`."""
    from repro.core import plan_skew_join
    from repro.core.executor import ShardedJoinExecutor
    mesh = jax.make_mesh((4,), ("cells",), devices=jax.devices()[:4],
                         axis_types=(AxisType.Auto,))
    q = QUERIES["b"]
    data = skewed_join_dataset(q, 300, 800, seed=4)
    ex = ShardedJoinExecutor(plan_skew_join(q, data, 4), mesh,
                             config=ExecutorConfig(overlap_shuffle=2))
    ses = ex.session().prepare(data)
    text = ex._compiled_step(ses._shapes, ses.caps, ses.cap_out).lower(
        ses._ptable_dev, *ses._device_args).compile().as_text()
    ops = [m.groups() for m in map(OP_NAME.match, text.splitlines()) if m]
    packs = [p for _, p in ops if "scatter_pack" in p]
    assert packs and all("phase_map" in p for p in packs)
    a2a = [p for name, p in ops if name.startswith("all-to-all")]
    assert len(a2a) >= 2 * len(q.relations)
    assert all("phase_exchange" in p for p in a2a)
