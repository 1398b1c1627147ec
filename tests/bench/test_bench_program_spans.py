"""The program's spans and op-name paths read from a trace, and each reader
built on them: on a hand-made trace with known answers, and on one with
neither (a program that records no spans), where every reader gives None."""
import json
import types

import pytest

from bench import program_spans as P
from bench import spec as S
from bench import trace_reduce as T
from bench.tools import gaps

HOST, DEV = "/host:CPU", "/device:TPU:0"
CACHE = "jit(serve_step)/while/body/attention/cache_insert/mul:"
MLP = "jit(serve_step)/while/body/closed_call/mlp/dot_general:"
# a TPU trace names an operation by its HLO text
FUSION_1 = ("%multiply_add_fusion.5 = bf16[32,2048] fusion(bf16[32,2048] "
            "%a), kind=kLoop, calls=%fused_computation.20")
FUSION_2 = "%fusion.2 = bf16[32,8] fusion(bf16[32,8] %b), kind=kOutput"

# (line, name, start ns, duration ns)
HOST_EVENTS = [
    ("python", "bench/window", 0, 10000),
    ("python", "acai/train/init", 100, 200),
    ("python", "acai/train/register", 300, 50),
    ("python", "acai/train/init", 400, 200),
    ("python", "acai/train/register", 600, 50),
    ("python", "acai/ckpt/save", 1000, 3000),
    ("python", "acai/ckpt/fetch", 1000, 500),
    ("python", "acai/ckpt/encode", 1500, 1200),
    ("python", "acai/lake/put", 2700, 1200),
    ("python", "acai/serve/dispatch", 4900, 100),
    ("python", "acai/serve/sync", 5000, 1000),
    ("python", "acai/serve/host", 6000, 800),
    ("python", "acai/serve/dispatch", 6900, 100),
    ("python", "acai/serve/sync", 7000, 1000),
    ("python", "acai/serve/host", 8000, 200),
    ("python", "not-ours", 9000, 10),
]
DEVICE_EVENTS = [
    (T.MODULES_LINE, "jit_serve_step(1)", 5000, 1000),
    (T.MODULES_LINE, "jit_serve_step(1)", 7000, 1000),
    (T.OPS_LINE, FUSION_1, 5000, 400),
    (T.OPS_LINE, FUSION_2, 5400, 600),
    (T.OPS_LINE, FUSION_1, 7000, 300),
    (T.OPS_LINE, FUSION_2, 7300, 700),
    (T.OPS_LINE, "%copy.3 = bf16[8] copy(bf16[8] %c)", 9500, 100),
]
OP_PATHS = {FUSION_1: CACHE, FUSION_2: MLP}


def _plane(pid, name, events, paths):
    """Text proto of one XPlane; op paths go on the event metadata, as the
    TPU profiler writes them."""
    names = sorted({n for _, n, _, _ in events})
    meta = {n: i + 1 for i, n in enumerate(names)}
    lines = []
    for k, line in enumerate(sorted({ln for ln, _, _, _ in events})):
        evs = "".join(
            f"events {{ metadata_id: {meta[n]} offset_ps: {s * 1000} "
            f"duration_ps: {d * 1000} }} "
            for ln, n, s, d in events if ln == line)
        lines.append(f'lines {{ id: {k} name: "{line}" timestamp_ns: 0 '
                     f'{evs}}}')
    metas = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" '
        + (f'stats {{ metadata_id: 1 str_value: "{paths[n]}" }} '
           if n in paths else "") + "} } "
        for n, i in meta.items())
    return (f'planes {{ id: {pid} name: "{name}" {" ".join(lines)} {metas}'
            'stat_metadata { key: 1 value { id: 1 name: "tf_op" } } }')


def _write_trace(directory, host, device, paths):
    from jax.profiler import ProfileData

    text = _plane(1, HOST, host, {}) + _plane(2, DEV, device, paths)
    out = directory / "trace" / "plugins" / "profile" / "1"
    out.mkdir(parents=True)
    (out / "h.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return directory / "trace"


def _run(directory, host, device, paths):
    trace = _write_trace(directory, host, device, paths)
    events = T.load_events(str(trace))
    return types.SimpleNamespace(work=directory, state={},
                                 trace_events=events,
                                 trace_summary=T.summary(events))


NEW = ["ckpt_fetch_s.train", "ckpt_encode_s.train", "lake_put_s.train",
       "train_init_s.sweep", "serve_host_ms.generate",
       "cache_insert_share.generate"]


def test_loader_reads_spans_and_op_paths(tmp_path):
    trace = _write_trace(tmp_path, HOST_EVENTS, DEVICE_EVENTS, OP_PATHS)
    path = P.newest_xplane(str(trace))
    spans = P.load_spans(path)
    assert [s["name"] for s in spans] == [
        n for _, n, _, _ in HOST_EVENTS if n.startswith("acai/")]
    assert P.op_paths(path) == {DEV: OP_PATHS}


def test_readers_on_a_hand_made_trace(tmp_path):
    run = _run(tmp_path, HOST_EVENTS, DEVICE_EVENTS, OP_PATHS)
    got = {m: S.metric_reader(m)(run) for m in NEW}
    assert got == pytest.approx({
        "ckpt_fetch_s.train": 500e-9,
        "ckpt_encode_s.train": 1200e-9,
        "lake_put_s.train": 1200e-9,
        "train_init_s.sweep": (200 + 50) * 1e-9,
        "serve_host_ms.generate": (100 + 800 + 100 + 200) / 2 * 1e-6,
        "cache_insert_share.generate": 100.0 * (400 + 300) / 2000,
    })
    assert "program_spans" in run.state      # read once, then kept


@pytest.mark.parametrize("paths", [
    {},
    {FUSION_1: "jit(serve_step)/while/body/mul:", FUSION_2: MLP},
], ids=["no_op_paths", "no_cache_insert_scope"])
def test_readers_give_none_for_a_program_without_spans(tmp_path, paths):
    host = [e for e in HOST_EVENTS if not e[1].startswith("acai/")]
    run = _run(tmp_path, host, DEVICE_EVENTS, paths)
    assert {m: S.metric_reader(m)(run) for m in NEW} == dict.fromkeys(NEW)


def test_gaps_are_named_by_the_innermost_program_span(tmp_path, capsys):
    trace = _write_trace(tmp_path, HOST_EVENTS, DEVICE_EVENTS, OP_PATHS)
    assert gaps.main([str(trace)]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # idle: [0, 5000), [6000, 7000), [8000, 9500), [9600, 10000)
    assert out["idle_s"] == pytest.approx(7900e-9)
    assert out["gaps"][0] == ["acai/ckpt/encode", pytest.approx(5000e-9)]
    assert out["gaps"][1] == ["(no span)", pytest.approx(1500e-9)]
    assert out["gaps"][2] == ["acai/serve/host", pytest.approx(1000e-9)]
    # covered: train spans 100-350 and 400-650, the save 1000-4000,
    # serve 4900-5000, 6000-6800 and 6900-7000, 8000-8200
    covered = 250 + 250 + 3000 + 100 + 800 + 100 + 200
    assert out["uncovered_share"] == pytest.approx(1 - covered / 7900)
