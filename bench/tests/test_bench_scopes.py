"""The training step's named scopes, from the program to the per-layer
metrics: every matmul and every gather and scatter of the step lies in a
scope, the scopes leave the compiled program as it was, the trace's
metadata gives each op its path, and device time is charged to the
innermost scope."""
import contextlib
import json
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, scopes, trace, xspace
from bench.arch import qwen2
from bench.tests import tiny

DATA = Path(__file__).parent / "data"
SMALL = DATA / "tpu_small.xplane.pb"
SCOPED = DATA / "tpu_scoped.xplane.pb"
WORKLOADS = Path(__file__).resolve().parents[1] / "workloads"
METRICS = Path(__file__).resolve().parents[1] / "metrics"
TRAIN_SCOPES = tuple(json.loads((WORKLOADS / "train.qwen1.5-0.5b.seq2048.json"
                                 ).read_text())["scopes"])
DDP_SCOPES = tuple(json.loads((WORKLOADS / "ddp4.qwen2-0.5b.adaptive.json"
                               ).read_text())["scopes"])
SEQ, ROWS = 32, 4

# one HLO instruction: its name, opcode and metadata
INSTR = re.compile(r"^\s*(?:ROOT )?%?(\S+) = .*? ([a-z][\w-]*)\(.*?"
                   r"metadata=\{[^}]*op_name=\"([^\"]*)\"")
METADATA = re.compile(r",? metadata=\{(?:[^{}\"]|\"(?:[^\"\\]|\\.)*\")*\}")


def _shapes(cfg):
    from repro.models.transformer import init_params
    from repro.optim import make_optimizer
    opt_init, opt_update = make_optimizer("adam", weight_decay=0.01)
    p = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((ROWS, SEQ), jnp.int32),
             "labels": jax.ShapeDtypeStruct((ROWS, SEQ), jnp.int32),
             "sample_weights": jax.ShapeDtypeStruct((ROWS,), jnp.float32)}
    return p, jax.eval_shape(opt_init, p), batch, opt_update


def train_hlo() -> str:
    """The compiled HLO of the benchmark's train step on the tiny
    configuration, built as ``bench/drivers/train_step.py`` builds it."""
    from repro.launch.train import train_ctx
    from repro.optim import warmup_cosine
    from repro.train import make_train_step
    cfg = qwen2.model_config(tiny.TINY_CONFIG)
    p, o, batch, opt_update = _shapes(cfg)
    fn = jax.jit(make_train_step(cfg, train_ctx(SEQ), opt_update,
                                 warmup_cosine(1e-3, 2, 10)),
                 donate_argnums=(0, 1))
    step = jax.ShapeDtypeStruct((), jnp.int32)
    return fn.lower(p, o, batch, step).compile().as_text()


def ddp_hlo() -> str:
    """The compiled HLO of the DDP compressed program on the tiny
    configuration, on a one-device mesh."""
    from jax.sharding import Mesh

    from repro.launch.train import train_ctx
    from repro.optim import warmup_cosine
    from repro.train.ddp import make_ddp_steps
    cfg = qwen2.model_config(tiny.TINY_CONFIG)
    p, o, batch, opt_update = _shapes(cfg)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    _, comp, _, _ = make_ddp_steps(cfg, train_ctx(SEQ), mesh, opt_update,
                                   warmup_cosine(1e-3, 2, 10), 0.1, p)
    rates = jax.ShapeDtypeStruct((1,), jnp.float32)
    step = jax.ShapeDtypeStruct((), jnp.int32)
    return jax.jit(comp).lower(p, o, batch, rates, step).compile().as_text()


def stripped(hlo: str) -> str:
    """The program without its debug information: each instruction's
    metadata, and the tables of source locations that metadata points
    into."""
    out, skip = [], False
    for line in hlo.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif line.startswith(("%", "ENTRY")):
            skip = False
        if not skip:
            out.append(METADATA.sub("", line))
    return "\n".join(out)


def instructions(hlo: str):
    """(name, opcode, scope path) of each instruction with metadata."""
    return [m.groups() for m in map(INSTR.match, hlo.splitlines()) if m]


@pytest.fixture(scope="module")
def train_text():
    return train_hlo()


@pytest.mark.parametrize("which", ["train", "ddp"])
def test_matmuls_gathers_and_scatters_lie_in_scopes(which, train_text):
    hlo = train_text if which == "train" else ddp_hlo()
    names = TRAIN_SCOPES if which == "train" else DDP_SCOPES
    found = set()
    for name, opcode, path in instructions(hlo):
        scope = scopes.scope_of(path, names)
        found.add(scope)
        if opcode in ("dot", "convolution"):
            assert scope in ("attention", "mlp", "vocab"), (name, path)
        elif opcode in ("gather", "scatter"):
            assert scope in ("vocab", "topk", "scatter_add"), (name, path)
    assert set(names) <= found


def test_scopes_leave_the_compiled_step_unchanged(train_text, monkeypatch):
    jax.clear_caches()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = train_hlo()
    jax.clear_caches()
    assert "/attention/" not in plain and "/attention/" in train_text
    assert stripped(plain) == stripped(train_text)


def _tf_ops_by_protobuf(path: Path) -> dict:
    """The ``tf_op`` stats as TensorFlow's own ``XSpace`` message reads
    them, in a child process: TensorFlow stays out of this one."""
    code = (
        "import json, sys\n"
        "from tensorflow.tsl.profiler.protobuf import xplane_pb2\n"
        "xs = xplane_pb2.XSpace()\n"
        "xs.ParseFromString(open(sys.argv[1], 'rb').read())\n"
        "out = {}\n"
        "for p in xs.planes:\n"
        "    if not p.name.startswith('/device:TPU:'):\n"
        "        continue\n"
        "    ids = {k for k, m in p.stat_metadata.items()"
        " if m.name == 'tf_op'}\n"
        "    out[p.name] = {m.name: s.str_value"
        " for m in p.event_metadata.values() for s in m.stats"
        " if s.metadata_id in ids}\n"
        "print(json.dumps(out))\n")
    r = subprocess.run([sys.executable, "-c", code, str(path)],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_xspace_reads_the_tf_op_paths():
    got = xspace.event_stats(SMALL.read_bytes(), "tf_op", "/device:TPU:")
    paths = {trace.op_name(k): v for k, v in got["/device:TPU:0"].items()}
    assert paths["broadcast_add_fusion"] == "jit(add)/add:"
    assert paths["fusion"] == "jit(<lambda>)/dot_general:"
    assert got == _tf_ops_by_protobuf(SMALL)


def test_xspace_rejects_a_cut_file():
    data = SMALL.read_bytes()
    with pytest.raises((ValueError, IndexError)):
        xspace.event_stats(data[:len(data) // 2], "tf_op", "/device:TPU:")


def _paths_by_op(tr, dev="/device:TPU:0"):
    return {op: p for (_, _, op), p in zip(tr["devices"][dev],
                                           tr["op_paths"][dev])}


def test_read_keeps_each_ops_path():
    tr = scopes.read(str(SMALL), ())
    dev = "/device:TPU:0"
    assert len(tr["op_paths"][dev]) == len(tr["devices"][dev])
    paths = _paths_by_op(tr)
    assert paths["fusion"] == "jit(<lambda>)/dot_general"
    # copies carry no path
    assert paths["copy-start"] == ""


@pytest.mark.parametrize("path,want", [
    ("jit(f)/attention/dot_general", "attention"),
    ("jit(f)/jvp(vocab)/reduce_sum", "vocab"),
    ("jit(f)/transpose(jvp(attention))/dot_general", "attention"),
    ("jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/dot_general", "mlp"),
    ("jit(f)/optimizer/mlp/add", "mlp"),
    ("jit(f)/attention/while/body/dynamic_slice", "attention"),
    ("jit(f)/transpose(jvp())/while/body/dynamic_update_slice", "unscoped"),
    ("jit(f)/attentions/add", "unscoped"),
    ("jit(mlp_helper)/add", "unscoped"),
    ("", "unscoped"),
])
def test_scope_of_takes_the_innermost_name(path, want):
    assert scopes.scope_of(path, scopes.STEP_SCOPES) == want


def _hand_trace(**changed):
    # chip a: a while loop (0..100) around an attention and an mlp op, a
    # vocab op from the backward, an unscoped op, and an op with no path;
    # chip b: one optimizer op.  The window leaves out the op at 300.
    # ``changed`` gives chip a's op ``fusion_2`` (``fusion.2``) another path.
    devices = {
        "/device:TPU:0": [[0, 100, "while.1"], [10, 30, "fusion.1"],
                          [50, 20, "fusion.2"], [120, 10, "fusion.3"],
                          [140, 5, "fusion.4"], [150, 8, "copy.1"],
                          [300, 9, "fusion.1"]],
        "/device:TPU:1": [[0, 40, "fusion.9"]]}
    paths = {
        "/device:TPU:0": {
            "while.1": "jit(s)/transpose(jvp())/while",
            "fusion.1": "jit(s)/jvp()/while/body/closed_call/"
                        "attention/attention/dot_general",
            "fusion.2": "jit(s)/transpose(jvp())/while/body/"
                        "closed_call/checkpoint/mlp/dot_general",
            "fusion.3": "jit(s)/transpose(jvp(vocab))/"
                        "jit(_take)/scatter-add",
            "fusion.4": "jit(s)/transpose(jvp())/add_any",
            **{k.replace("_", "."): v for k, v in changed.items()}},
        "/device:TPU:1": {"fusion.9": "jit(s)/optimizer/add"}}
    return {"window": [0, 250], "host": [], "devices": devices,
            "op_paths": {d: [paths[d].get(op, "") for _, _, op in evs]
                         for d, evs in devices.items()}}


def test_scope_seconds_charges_self_time_to_the_innermost_scope():
    got = scopes.scope_seconds(_hand_trace(), scopes.STEP_SCOPES)
    # mean over the two chips
    assert got == {"attention": pytest.approx(15e-9),
                   "mlp": pytest.approx(10e-9),
                   "vocab": pytest.approx(5e-9),
                   "optimizer": pytest.approx(20e-9),
                   "unscoped": pytest.approx((50 + 5 + 8) / 2 * 1e-9)}
    ops = scopes.scope_op_seconds(_hand_trace(), scopes.STEP_SCOPES)
    assert ops["unscoped"] == {"while.1": pytest.approx(25e-9),
                               "fusion.4": pytest.approx(2.5e-9),
                               "copy.1": pytest.approx(4e-9)}
    # the scopes and the rest add up to the ops' self time
    assert sum(got.values()) == pytest.approx(
        sum(trace.op_seconds(_hand_trace()).values()))


def test_step_scope_ms_gives_nothing_without_scopes():
    tr = _hand_trace()
    names = scopes.STEP_SCOPES
    assert scopes.step_scope_ms(None, names, 2) is None
    assert scopes.step_scope_ms(tr, names, 0) is None
    # a trace without paths, and a program that names no scope: every
    # path, none of the names
    for path in ("", "jit(s)/add"):
        plain = {d: [path] * len(ps) for d, ps in tr["op_paths"].items()}
        assert scopes.step_scope_ms(dict(tr, op_paths=plain), names,
                                    2) is None


def test_step_scope_ms_adds_up_to_the_scoped_time():
    tr = _hand_trace()
    secs = scopes.scope_seconds(tr, scopes.STEP_SCOPES)
    assert scopes.step_scope_ms(tr, scopes.STEP_SCOPES, 2) == {
        k: pytest.approx(v / 2 * 1e3) for k, v in secs.items()}


@pytest.fixture(scope="module")
def scoped():
    raw = scopes.read(str(SCOPED), ("input", "dispatch", "metrics_read"))
    lo = min(h[0] for h in raw["host"])
    hi = max(h[0] + h[1] for h in raw["host"])
    return dict(raw, window=[lo, hi])


def test_a_chip_trace_names_the_matmul_attention(scoped):
    paths = _paths_by_op(scoped)
    matmuls = {op: p for op, p in paths.items() if "dot_general" in p}
    assert matmuls
    for op, p in matmuls.items():
        assert scopes.scope_of(p, scopes.STEP_SCOPES) == "attention", (op, p)
    assert any("jvp(attention)/" in p for p in matmuls.values())
    assert any("transpose(jvp(attention))/" in p for p in matmuls.values())
    secs = scopes.scope_seconds(scoped, scopes.STEP_SCOPES)
    # XLA fuses the update into the backward matmul, and a fusion carries
    # one path: no op is left in the optimizer scope
    assert set(secs) == {"attention", "unscoped"}
    assert secs["attention"] > 0
    assert sum(secs.values()) == pytest.approx(
        sum(trace.op_seconds(scoped).values()))


def test_a_chip_trace_bounds_its_clock(scoped):
    clock = scopes.clock_offset_ns(scoped)["/device:TPU:0"]
    lo, hi = clock["bracket"]
    assert clock["pairs"] == 6
    assert 0 < lo < clock["offset"] < hi < lo + 0.5e6


def test_summary_of_a_chip_trace(scoped):
    got = scopes.summary(scoped)
    assert got["steps"] == 3
    ms = got["scope_ms_per_step"]
    assert ms["attention"] > 0 and ms["mlp"] == ms["vocab"] == 0
    assert sum(ms.values()) == pytest.approx(
        sum(trace.op_seconds(scoped).values()) / 3 * 1e3)
    assert {op for op, _, _ in got["unscoped_ops"]} == set(
        scopes.scope_op_seconds(scoped, scopes.STEP_SCOPES)["unscoped"])
    assert got["clock_pairs"] == 6
    assert sum(v for _, v in got["idle_gaps_aligned"]) == pytest.approx(
        sum(v for _, v in got["idle_gaps"]))


def test_the_command_reduces_a_launcher_capture(tmp_path, capsys):
    from repro.launch import train
    train.run(train.parse_args(
        ["--arch", "qwen2-0.5b", "--reduced", "--steps", "4", "--batch", "2",
         "--seq", "32", "--scadles", "--trace-dir", str(tmp_path)]))
    files = list(tmp_path.rglob("*.xplane.pb"))
    assert len(files) == 1
    capsys.readouterr()
    assert scopes.main([str(files[0])]) == 0
    got = json.loads(capsys.readouterr().out)
    # the window runs from the first captured step to the last; the CPU
    # has no TPU plane, so nothing is scoped and no clock is bounded
    assert got["steps"] == 3 and got["window_s"] > 0
    assert got["scope_ms_per_step"] is None
    assert got["clock_pairs"] == 0 and got["clock_bracket_ms"] is None


def test_the_command_needs_a_window():
    with pytest.raises(RuntimeError, match="window"):
        scopes.load(str(SCOPED))


def _scope_reader(name):
    return harness.load_module(METRICS / f"{name}_ms_per_step.py")


def _run(tr, names, steps):
    """What a scope reader needs of ``bench/harness.Run``."""
    from types import SimpleNamespace
    return SimpleNamespace(trace=tr, done=[0.0] * steps,
                           cell=SimpleNamespace(spec={"scopes": list(names)}))


@pytest.mark.parametrize("names", [TRAIN_SCOPES, DDP_SCOPES,
                                   ("mlp", "vocab", "optimizer")])
def test_scope_readers_and_unscoped_add_up_to_busy_time(scoped, names):
    run = _run(scoped, names, 3)
    got = {s: _scope_reader(s).read(run) for s in DDP_SCOPES}
    # a reader reads nothing where the cell does not list its scope, or
    # where no op of the window lies in it
    assert {s for s, v in got.items() if v is not None} == (
        {"attention"} & set(names))
    unscoped = scopes.scope_seconds(scoped, names)["unscoped"] / 3 * 1e3
    busy = trace.busy(scoped)["/device:TPU:0"] / 3 * 1e3
    assert sum(v for v in got.values() if v) + unscoped == pytest.approx(
        busy, rel=1e-9)


def test_a_scope_left_out_goes_to_the_scope_around_it():
    tr = _hand_trace(fusion_2="jit(s)/transpose(jvp())/optimizer/"
                              "checkpoint/mlp/dot_general")
    run = _run(tr, TRAIN_SCOPES, 2)
    assert _scope_reader("mlp").read(run) == pytest.approx(10e-9 / 2 * 1e3)
    assert _scope_reader("optimizer").read(run) == pytest.approx(
        20e-9 / 2 * 1e3)
    # without "mlp" its op is the optimizer's; without "attention" too,
    # the attention ops, in no other listed scope, go to "unscoped"
    run = _run(tr, ("vocab", "optimizer"), 2)
    assert _scope_reader("mlp").read(run) is None
    assert _scope_reader("attention").read(run) is None
    assert _scope_reader("optimizer").read(run) == pytest.approx(
        30e-9 / 2 * 1e3)
    assert scopes.scope_seconds(tr, ("vocab", "optimizer"))["unscoped"] == \
        pytest.approx((50 + 5 + 8 + 30) / 2 * 1e-9)


def test_scope_readers_need_a_trace():
    run = _run(None, TRAIN_SCOPES, 3)
    assert _scope_reader("attention").read(run) is None
    assert _scope_reader("attention").read(
        _run(_hand_trace(), TRAIN_SCOPES, 0)) is None


def test_an_op_name_of_two_programs_takes_each_events_own_path():
    # two programs in one window each have a fusion.1: the trace's
    # per-event paths keep them apart where the op name cannot
    tr = {"window": [0, 100], "host": [],
          "devices": {"/device:TPU:0": [[0, 10, "fusion.1"],
                                        [20, 30, "fusion.1"]]},
          "op_paths": {"/device:TPU:0": ["jit(a)/mlp/dot",
                                         "jit(b)/topk/sort"]}}
    assert scopes.scope_op_seconds(tr, DDP_SCOPES) == {
        "mlp": {"fusion.1": pytest.approx(10e-9)},
        "topk": {"fusion.1": pytest.approx(30e-9)}}


def test_a_runs_scope_readers_share_one_pass(monkeypatch):
    calls = []
    real = scopes.scope_seconds
    monkeypatch.setattr(scopes, "scope_seconds",
                        lambda tr, names: calls.append(names) or real(tr, names))
    tr = _hand_trace()
    run = _run(tr, TRAIN_SCOPES, 2)
    got = {s: _scope_reader(s).read(run) for s in TRAIN_SCOPES}
    assert calls == [tuple(TRAIN_SCOPES)]
    assert got == {s: pytest.approx(v / 2 * 1e3)
                   for s, v in real(tr, TRAIN_SCOPES).items()
                   if s != "unscoped"}
    # another trace, or the same one under other names, is read anew
    _scope_reader("mlp").read(_run(_hand_trace(), TRAIN_SCOPES, 2))
    _scope_reader("mlp").read(_run(tr, ("mlp",), 2))
    assert len(calls) == 3
