"""The trace reduction, on a synthesized trace of two TPUs and a host, and
on a real profiler trace of the CPU, which holds no device to count."""
import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from bench import tracereduce

MS = 1_000_000          # ns
STEP = "jit(step)/shard_map"

# (device, start ms, duration ms, HLO op, module, op_name path)
OPS = [
    (0, -1.0, 0.5, "fusion.1", "jit_step", f"{STEP}/jit(scatter_pack_host)/x"),
    (0, 0.0, 2.0, "sort.2", "jit_step", f"{STEP}/jit(scatter_pack_host)/sort"),
    (0, 2.5, 2.0, "sort.3", "jit_step", f"{STEP}/jit(join_probe_host)/sort"),
    (0, 4.0, 1.0, "fusion.4", "jit_step", f"{STEP}/jit(expand_rows)/add"),
    (0, 6.0, 1.0, "all-to-all.5", "jit_step", f"{STEP}/all_to_all"),
    (0, 8.0, 1.0, "copy.6", "jit_count_matrices", "jit(count_matrices)/x"),
    (0, 9.5, 1.0, "fusion.7", "jit_step", f"{STEP}/jit(step_tail)/y"),
    (1, 0.0, 4.0, "sort.2", "jit_step", f"{STEP}/jit(scatter_pack_host)/sort"),
    (1, 6.0, 3.0, "all-to-all.5", "jit_step", f"{STEP}/all_to_all"),
]
# (start ms, duration ms, name) on the host's main thread.
HOST = [
    (0.0, 10.0, "bench.window"),
    (0.0, 9.0, "bench.step_round"),
    (5.0, 1.0, "PjitFunction(step)"),
    (5.2, 0.5, "device_put"),
    (7.0, 1.0, "numpy filter"),
    (9.0, 1.0, "bench.submit"),
]
T0_NS = 1_000 * MS


def _text_proto() -> str:
    names = sorted({o[3] for o in OPS} | {h[2] for h in HOST})
    meta = {n: i + 1 for i, n in enumerate(names)}
    strs = sorted({o[4] for o in OPS} | {o[5] for o in OPS})
    ref = {s: 100 + i for i, s in enumerate(strs)}

    def event_meta():
        return "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{n}" }} }}\n' for n, i in meta.items())

    def stat_meta():
        out = ('stat_metadata { key: 1 value { id: 1 name: "hlo_module" } }\n'
               'stat_metadata { key: 2 value { id: 2 name: "tf_op" } }\n')
        return out + "".join(f'stat_metadata {{ key: {i} value {{ id: {i} '
                             f'name: "{s}" }} }}\n' for s, i in ref.items())

    planes = []
    for dev in (0, 1):
        evs = "".join(
            f"events {{ metadata_id: {meta[op]} "
            f"offset_ps: {int((start * MS + 2 * MS) * 1000)} "
            f"duration_ps: {int(dur * MS * 1000)} "
            f"stats {{ metadata_id: 1 ref_value: {ref[mod]} }} "
            f"stats {{ metadata_id: 2 ref_value: {ref[path]} }} }}\n"
            for d, start, dur, op, mod, path in OPS if d == dev)
        planes.append(
            f'planes {{ id: {dev + 1} name: "/device:TPU:{dev}"\n'
            f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: '
            f'{T0_NS - 2 * MS}\n{evs}}}\n'
            f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: {T0_NS}\n'
            f'events {{ metadata_id: {meta["sort.2"]} offset_ps: 0 '
            f'duration_ps: {10 * MS * 1000} }} }}\n'
            f'{event_meta()}{stat_meta()}}}\n')
    host = "".join(f"events {{ metadata_id: {meta[n]} "
                   f"offset_ps: {int(s * MS * 1000)} "
                   f"duration_ps: {int(d * MS * 1000)} }}\n"
                   for s, d, n in HOST)
    planes.append(f'planes {{ id: 9 name: "/host:CPU"\n'
                  f'lines {{ id: 1 name: "python" timestamp_ns: {T0_NS}\n'
                  f'{host}}}\n{event_meta()}}}\n')
    return "".join(planes)


@pytest.fixture(scope="module")
def trace():
    return tracereduce.from_profile(ProfileData.from_text_proto(_text_proto()))


def test_busy_time_is_the_union_inside_the_window(trace):
    assert trace.window_s() == pytest.approx(0.010)
    # device 0: [0,2] [2.5,5] [6,7] [8,9] [9.5,10] -> 7.0 ms (the op before
    # the window is clipped away, [9.5, 10.5] is clipped at 10);
    # device 1: [0,4] [6,9] -> 7.0 ms.
    assert trace.busy_s() == pytest.approx(0.007)


def test_phases_credit_each_busy_instant_once(trace):
    # map: device 0 2 ms, device 1 4 ms -> mean 3 ms.  reduce on device 0:
    # [2.5, 4.5] and the part of [4, 5] past it, 2.5 ms -> mean 1.25 ms.
    assert trace.phase_s(("scatter_pack",)) == pytest.approx(0.003)
    assert trace.phase_s(("join_probe", "expand_rows")) == pytest.approx(
        0.00125)
    assert trace.collective_s("all-to-all") == pytest.approx(0.002)
    # Device 0: all but count_matrices' 1 ms; device 1: all 7 ms.
    assert trace.module_s("jit_step") == pytest.approx((6 + 7) / 2 / 1e3)


def test_breakdown_labels(trace):
    top = dict(trace.top_ops(10))
    assert top["map/scatter_pack_host/sort"] == pytest.approx(0.003)
    assert top["reduce/join_probe_host/sort"] == pytest.approx(0.001)
    # [4, 5] less the [4, 4.5] that join_probe's sort already covers.
    assert top["reduce/expand_rows/add"] == pytest.approx(0.00025)
    assert top["step_other/all_to_all"] == pytest.approx(0.002)
    assert top["jit_count_matrices/count_matrices/x"] == pytest.approx(0.0005)
    assert top["step_other/step_tail/y"] == pytest.approx(0.00025)


def test_idle_gaps_named_by_host_activity(trace):
    gaps = dict(trace.idle_gaps(10))
    # device 0 is idle over [2, 2.5], [5, 6], [7, 8], [9, 9.5].
    # [5, 6]: the shortest span covering half of it, inside the step.
    assert gaps["bench.step_round > device_put"] == pytest.approx(0.001)
    assert gaps["bench.step_round > numpy filter"] == pytest.approx(0.001)
    assert gaps["bench.step_round"] == pytest.approx(0.0005)
    assert gaps["bench.submit"] == pytest.approx(0.0005)
    assert sum(gaps.values()) == pytest.approx(0.003)


def test_a_cpu_trace_holds_no_device(tmp_path):
    f = jax.jit(lambda x: jnp.sort(x) + 1)
    x = jnp.arange(1024)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tracereduce.WINDOW):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tracereduce.load_dir(str(tmp_path))
    assert t.devices == [] and t.busy_s() == 0.0 and t.window_s() > 0
    assert t.top_ops() == [] and t.idle_gaps() == []


def test_a_trace_without_the_window_is_an_error():
    with pytest.raises(ValueError):
        tracereduce.from_profile(ProfileData.from_text_proto(
            'planes { id: 1 name: "/host:CPU" }'))


def test_tpu_style_ops_take_module_and_op_name_from_elsewhere():
    """A TPU trace names each op by its HLO text and carries neither module
    nor op_name: the module is the `XLA Modules` event around the op, and
    the op_name comes from the compiled HLO text."""
    hlo = ('  %while.9 = (s32[]) while(s32[] %t), condition=%c, body=%b, '
           'metadata={op_name="jit(step)/jit(join_probe_host)/while" '
           'source_file="x.py"}\n'
           '  ROOT %fusion.2 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop, '
           'metadata={op_name="jit(step)/jit(scatter_pack_host)/scatter"}\n')
    names = tracereduce.op_names_from_hlo([hlo])
    assert names == {"while.9": "jit(step)/jit(join_probe_host)/while",
                     "fusion.2": "jit(step)/jit(scatter_pack_host)/scatter"}
    ev = ('events {{ metadata_id: {m} offset_ps: {o} duration_ps: {d} }}\n')
    ms = 1_000_000_000      # ps
    txt = (
        'planes { id: 1 name: "/device:TPU:0"\n'
        'lines { id: 1 name: "XLA Modules" timestamp_ns: 0\n'
        + ev.format(m=1, o=0, d=10 * ms) + '}\n'
        'lines { id: 2 name: "XLA Ops" timestamp_ns: 0\n'
        + ev.format(m=2, o=1 * ms, d=4 * ms)      # the while
        + ev.format(m=3, o=2 * ms, d=1 * ms)      # a body op inside it
        + ev.format(m=4, o=6 * ms, d=2 * ms)      # the map's fusion
        + '}\n'
        'event_metadata { key: 1 value { id: 1 name: "jit_step(123)" } }\n'
        'event_metadata { key: 2 value { id: 2 name: '
        '"%while.9 = (s32[]) while(s32[] %t)" } }\n'
        'event_metadata { key: 3 value { id: 3 name: '
        '"%fusion.7 = s32[8]{0} fusion(s32[8]{0} %q)" } }\n'
        'event_metadata { key: 4 value { id: 4 name: '
        '"%fusion.2 = s32[8]{0} fusion(s32[8]{0} %p)" } }\n}\n'
        'planes { id: 2 name: "/host:CPU"\n'
        'lines { id: 1 name: "python" timestamp_ns: 0\n'
        + ev.format(m=1, o=0, d=10 * ms) + '}\n'
        'event_metadata { key: 1 value { id: 1 name: "bench.window" } }\n}\n')
    t = tracereduce.from_profile(ProfileData.from_text_proto(txt),
                                 op_names=names)
    assert t.busy_s() == pytest.approx(0.006)
    assert t.module_s("jit_step") == pytest.approx(0.006)
    assert t.phase_s(("join_probe",)) == pytest.approx(0.004)
    assert t.phase_s(("scatter_pack",)) == pytest.approx(0.002)
    assert dict(t.top_ops()) == pytest.approx({
        "reduce/join_probe_host/while": 0.004,
        "map/scatter_pack_host/scatter": 0.002,
        "step_other/fusion": 0.0})
