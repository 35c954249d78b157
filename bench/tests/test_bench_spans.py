"""The readers of the program's spans and phase scopes, on a synthesized
trace of two TPUs and a host: the expected milliseconds where the spans
and scopes are there, and nothing where they are not."""
import pytest

from bench import run as harness
from bench.gen import RequestSpec
from bench.record import Run, Served
from bench.tracereduce import Op, Span, Trace

MS = 1_000_000          # ns
SCOPED = ("ingest_ms.batch", "egress_ms.batch", "idle_unspanned_ms.batch",
          "map_scope_ms.batch", "reduce_scope_ms.batch")

# (device, start ms, duration ms, op_name path) inside a 100 ms window.
OPS = [
    (0, 10, 20, "jit(step)/phase_map/jit(scatter_pack_host)/sort"),
    (0, 30, 40, "jit(step)/phase_reduce/jit(join_probe_host)/sort"),
    (0, 70, 5, "jit(step)/phase_exchange/reduce_sum"),
    (1, 10, 10, "jit(step)/phase_map/jit(scatter_pack_host)/sort"),
    (1, 30, 20, "jit(step)/phase_reduce/jit(expand_rows_host)/gather"),
]
# (start ms, duration ms, name).  Device 0 is idle over [0, 10] and
# [75, 100]; of that, [0, 2], [90, 94] and [98, 99] lie under no span but
# join.request and bench.step_round.
HOST = [
    (0, 100, "bench.step_round"),
    (0, 50, "join.request"),
    (2, 6, "join.ingest"),
    (8, 4, "join.dispatch"),
    (12, 62, "join.wait"),
    (74, 16, "join.egress"),
    (94, 4, "join.retry"),
    (95, 2, "join.ingest"),
    (99, 6, "join.egress"),         # runs past the window's end
]
# Two requests completed: ingest (6 + 2) / 2, egress (16 + 1) / 2, idle
# (2 + 4 + 1) / 2, map (20 + 10) / 2 chips / 2, reduce (40 + 20) / 2 / 2.
EXPECTED = {"ingest_ms.batch": 4.0, "egress_ms.batch": 8.5,
            "idle_unspanned_ms.batch": 3.5, "map_scope_ms.batch": 7.5,
            "reduce_scope_ms.batch": 15.0}


def _run(ops=OPS, host=HOST, devices=2) -> Run:
    run = Run("paper3-batch", devices, {"hbm_bytes_per_s": 1.0})
    for i in range(2):
        run.served.append(Served(RequestSpec(i, "paper3", 8, (0, i)),
                                 {"R": 8}, done=1.0 + i))
    run.served.append(Served(RequestSpec(2, "paper3", 8, (0, 2)), {"R": 8}))
    run.trace = Trace(
        (0.0, 100.0 * MS),
        [[Op(s * MS, d * MS, "op", "jit_step", path)
          for dev, s, d, path in ops if dev == k] for k in range(devices)],
        [Span(s * MS, d * MS, name) for s, d, name in host])
    return run


@pytest.mark.parametrize("metric", SCOPED)
def test_readers_on_a_synthesized_trace(metric):
    value = harness.metric_reader(metric)(_run())
    assert value == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", SCOPED)
def test_readers_read_nothing_where_the_program_opened_nothing(metric):
    """The parent program's trace: kernel names only, bench spans only."""
    ops = [(dev, s, d, path.replace("phase_map/", "").replace(
        "phase_reduce/", "").replace("phase_exchange/", ""))
        for dev, s, d, path in OPS]
    host = [h for h in HOST if not h[2].startswith("join.")]
    assert harness.metric_reader(metric)(_run(ops, host)) is None


@pytest.mark.parametrize("metric", SCOPED)
def test_readers_read_nothing_from_an_untraced_run(metric):
    run = _run()
    run.trace = None
    assert harness.metric_reader(metric)(run) is None


def test_a_dropped_span_reads_as_missing_not_zero():
    host = [h for h in HOST if h[2] != "join.egress"]
    run = _run(host=host)
    assert harness.metric_reader("egress_ms.batch")(run) is None
    assert harness.metric_reader("ingest_ms.batch")(run) == pytest.approx(4)


def test_idle_needs_a_device():
    """A CPU trace holds no device: idle is unknown, spans still read."""
    run = _run(devices=0)
    assert harness.metric_reader("idle_unspanned_ms.batch")(run) is None
    assert harness.metric_reader("ingest_ms.batch")(run) == pytest.approx(4)


def test_only_join_request_leaves_idle_unexplained():
    """With every span but join.request gone, all idle is unspanned."""
    host = [h for h in HOST if h[2] in ("join.request", "join.wait")]
    assert harness.metric_reader("idle_unspanned_ms.batch")(
        _run(host=host)) == pytest.approx(35 / 2)
