"""The readers of the program's own names (``chipbench.program_trace``,
``metrics/program.py``) on a hand-made trace whose answers are known: its
host spans (``repro.*``), GR-MAC launches named by site and ops named with
their operands; on op texts from a TPU v5e trace; on a small trace
recorded on a TPU v5e (the first 12 program runs of a traced chat window:
a bucket-128 and a bucket-32 prefill chunk, then decode steps); and the
serving programs' names as the harness reads them."""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import flops, harness, program_trace, trace
from chipbench.metrics import program

BENCH = Path(__file__).resolve().parents[2] / "chipbench"
SPEC = json.loads((BENCH / "configs" / "qwen2-1.5b-grmac.json").read_text())
PEAKS = flops.peaks("TPU v5 lite")
PROG = {"prefill": ["jit_prefill_b32", "jit_prefill_b128"],
        "decode": ["jit_decode_greedy"]}
LAYOUT = "{1,0:T(8,128)S(1)}"
NEW = ("cim_prep_share_pct.chat", "head_ms_per_prefill.chat",
       "host_idle_ms_per_step.chat", "prefill_row_use_pct.chat")


def _launch(name, m, k, n, weight):
    """A launch's HLO text as the TPU's trace gives it."""
    return (f"%{name} = f32[{m},{n}]{LAYOUT} custom-call(f32[{m},{k}]"
            f"{LAYOUT} %bitcast.1, f32[{k},{n}]{LAYOUT} %{weight}), "
            'custom_call_target="tpu_custom_call", operand_layout_'
            f"constraints={{f32[{m},{k}]{{1,0}}, f32[{k},{n}]{{1,0}}}}")


def _op(name, start, dur):
    return trace.Op(name, start, dur)


W_QKV = "bf16[28,1536,256] %get-tuple-element.5"
W_HEAD = "bf16[152064,1536] %get-tuple-element.9"


def _devices(host):
    ops = [
        # prefill 0..1000: the qkv weight's absmax (counted), the
        # activations' absmax (not), two weight re-quantisations, two
        # launches, the head weight's absmax (counted) and an embedding
        # lookup that reads the head's weight but makes no scalar (not)
        _op(f"%fusion.7 = f32[] fusion({W_QKV}, s32[] %get-tuple-element.1)",
            0, 20),
        _op("%fusion.8 = f32[] fusion(bf16[8,128,1536] %get-tuple-element.6)",
            20, 20),
        _op("%negate_select_fusion.1 = f32[1536,256] fusion(f32[] %max.3, "
            f"{W_QKV}, s32[] %get-tuple-element.1)", 40, 60),
        _op(_launch("grmac_attn_qkv.4", 1024, 1536, 256,
                    "negate_select_fusion.1"), 100, 300),
        _op("%negate_select_fusion.2 = f32[1536,152064] fusion(f32[] %max.4, "
            f"{W_HEAD})", 400, 50),
        _op(_launch("grmac_head.5", 1024, 1536, 152064,
                    "negate_select_fusion.2"), 450, 500),
        _op(f"%fusion.1 = f32[] fusion({W_HEAD})", 950, 30),
        _op(f"%gather.2 = bf16[1024,1536] gather({W_HEAD}, "
            "s32[1024] %get-tuple-element.3)", 980, 20),
        # idle 1000..1500; decode 1500..2000 (its own module's names): the
        # head's weight is a copy of the embedding, made in the program
        _op("%copy.2 = bf16[152064,1536] copy(bf16[152064,1536] %p.0)",
            1500, 20),
        _op("%negate_select_fusion.1 = f32[1536,152064] fusion(f32[] %max.1, "
            "bf16[152064,1536] %copy.2)", 1520, 30),
        _op(_launch("grmac_head", 128, 1536, 152064,
                    "negate_select_fusion.1"), 1550, 350),
        _op("%fusion.3 = f32[] fusion(bf16[152064,1536] %copy.2)", 1900, 100),
        # idle 2000..2600; decode 2600..2800
        _op("%negate_select_fusion.1 = f32[1536,152064] fusion(f32[] %max.1, "
            "bf16[152064,1536] %copy.2)", 2600, 50),
        _op(_launch("grmac_head", 128, 1536, 152064,
                    "negate_select_fusion.1"), 2650, 150),
    ]
    mods = [("jit_prefill_b128(3)", 0, 1000), ("jit__argmax(5)", 1450, 0),
            ("jit_decode_greedy(7)", 1500, 500),
            ("jit_decode_greedy(7)", 2600, 200)]
    return [trace.Device(mods, ops, host)]


# weights made: prefill 20 + 60 + 50 + 30, decode 20 + 30 + 100, then 50
PREP_NS = 160 + 150 + 50


def _prefill(slot, tokens, bucket, b=8):
    """A prefill dispatch as the harness logs it."""
    lens = np.zeros(b, np.int32)
    lens[slot] = tokens
    return ("prefill", np.zeros((b, bucket), np.int32),
            np.zeros(b, np.int32), lens, np.zeros((b, bucket), np.int32))


CHUNK = "repro.engine.prefill_chunk"
HOST = [
    # the harness's span around the whole step, the program's inside it
    ("chipbench.scheduler_step", -300, 2000),
    ("repro.scheduler.step", -200, 1900),
    ("repro.scheduler.prefill", -150, 1100),
    (CHUNK, -100, 150, {"slot": 0, "rid": 7, "tokens": 28, "bucket": 32}),
    ("repro.scheduler.decode", 1100, 600),
    ("repro.engine.step", 1110, 580),
    ("repro.engine.fetch", 1200, 480),          # holds the gap's middle
    ("repro.scheduler.step", 1950, 800),
    ("repro.scheduler.admit", 1960, 30, {"rid": 8}),
    ("repro.scheduler.prefill", 2000, 500),
    (CHUNK, 2100, 400, {"slot": 1, "rid": 8, "tokens": 100, "bucket": 128}),
]
EVENTS = [_prefill(0, 28, 32), _prefill(1, 100, 128)]


def _run(host=HOST, programs=PROG):
    return trace.RunData(_devices(list(host)), window_s=3e-6,
                         programs=programs, events=EVENTS, spec=SPEC,
                         peaks=PEAKS, chips=1)


def test_sites_and_weight_operands_are_read_from_launch_names():
    ops = _devices([])[0].ops
    launches = [o for o in ops if trace.is_kernel(o)]
    assert [program_trace.kernel_site(o) for o in launches
            ] == ["attn_qkv", "head", "head", "head"]
    assert program_trace.kernel_weight(launches[0]) == \
        "%negate_select_fusion.1"
    assert program_trace.kernel_site(ops[0]) is None
    unsited = _op(_launch("grmac_matmul_pallas.9", 128, 1536, 256, "w.1"),
                  0, 1)
    assert program_trace.kernel_site(unsited) is None
    assert program_trace.kernel_ns_by_site(ops) == {"attn_qkv": 300,
                                                    "head": 1000}


def test_idle_gaps_take_the_innermost_span():
    run = _run()
    idle = program_trace.idle_by_span(run.devices[0].ops, HOST)
    # 1000..1500 (middle 1250): inside engine.fetch inside engine.step;
    # 2000..2600 (middle 2300): inside the prefill chunk's dispatch
    assert idle == {"repro.engine.fetch": 500, CHUNK: 600}
    assert program_trace.idle_in_steps(run.devices[0].ops, HOST) == (1100, 2)
    # where the host is in no span the gap has no label
    assert program_trace.idle_by_span(run.devices[0].ops, []) == {None: 1100}


def test_a_spans_self_time_leaves_out_its_children():
    own = program_trace.self_ns(HOST)
    assert own["repro.engine.step"] == (1, 580, 100)
    assert own["repro.scheduler.decode"] == (1, 600, 20)
    # two steps, 1900 + 800 ns, less prefill 1100, decode 600, admit 30
    # and prefill 500
    assert own["repro.scheduler.step"] == (2, 2700, 470)
    assert own["chipbench.scheduler_step"] == (1, 2000, 100)
    assert own[CHUNK] == (2, 550, 550)


def test_the_new_readers_on_the_known_trace():
    run = _run()
    # programs 1000 + 700 ns
    assert program.cim_prep_share_pct(run) == pytest.approx(
        100 * PREP_NS / 1700)
    assert program.head_ms_per_prefill(run) == pytest.approx(500 / 1e6)
    assert program.host_idle_ms_per_step(run) == pytest.approx(550 / 1e6)
    assert program.prefill_row_use_pct(run) == pytest.approx(
        100 * 128 / 1280)
    bench = harness.benchmark()
    got = harness.read_layer_metrics(bench, "qwen2-1.5b.chat", run)
    assert set(NEW) <= set(got)


def test_a_run_without_the_programs_names_reads_nothing(tmp_path):
    """The parent program: anonymous programs, unsited launches, only the
    harness's spans, and no trace file of this run."""
    host = [h for h in HOST if h[0].startswith("chipbench.")]
    run = _run(host, {"prefill": ["jit__lambda"], "decode": ["jit_fn"]})
    d = run.devices[0]
    d.modules[:] = [("jit__lambda(3)",) + m[1:] if "prefill" in m[0]
                    else ("jit_fn(7)",) + m[1:] if "decode" in m[0] else m
                    for m in d.modules]
    d.ops[:] = [trace.Op(o.name.replace("%grmac_head", "%grmac_matmul_pallas"
                                        ).replace("%grmac_attn_qkv",
                                                  "%grmac_matmul_pallas"),
                         o.start, o.dur) for o in d.ops]
    assert program_trace.program_spans(run, tmp_path) == []
    assert program.head_ms_per_prefill(run) is None
    # the rows come from the harness's log of the dispatches, as for any
    # program
    assert program.prefill_row_use_pct(run) == pytest.approx(
        100 * 128 / 1280)
    # the weight operands are named in any program's launches
    assert program.cim_prep_share_pct(run) == pytest.approx(
        100 * PREP_NS / 1700)
    empty = trace.RunData([], window_s=1.0, programs=PROG, events=[],
                          spec=SPEC, peaks=PEAKS, chips=1)
    for name in NEW:
        assert harness.metric_reader(name)(empty) is None


# ops of a prefill program as the TPU's trace names them (PR 12's chip
# trace): the activations' absmax, a weight's absmax, its re-quantisation,
# the launch that reads it, the launch's rescaling, a fusion with a tuple
# result
TPU_OPS = [
    "%fusion.311 = f32[]{:T(128)} fusion(f32[1536]{0:T(1024)S(1)} "
    "%convert_reduce_fusion.4, f32[8,128]{1,0:T(8,128)S(1)} %add_sqrt_fusion"
    ".5, bf16[8,128,1536]{2,1,0:T(8,128)(2,1)S(1)} %get-tuple-element.840), "
    "kind=kLoop, calls=%fused_computation.217.clone.clone",
    "%fusion.312 = f32[]{:T(128)} fusion(bf16[28,1536,256]{2,1,0:T(8,128)"
    "(2,1)} %get-tuple-element.868, s32[]{:T(128)} %get-tuple-element.839), "
    "kind=kLoop, calls=%fused_computation.272.clone.clone",
    "%negate_select_fusion.15 = f32[1536,256]{1,0:T(8,128)S(1)} fusion(f32[]"
    "{:T(128)S(6)} %max.756, bf16[28,1536,256]{2,1,0:T(8,128)(2,1)} "
    "%get-tuple-element.868, s32[]{:T(128)} %get-tuple-element.839), "
    "kind=kLoop, calls=%fused_computation.239.clone.clone",
    "%grmac_matmul_pallas.93 = f32[1024,256]{1,0:T(8,128)S(1)} custom-call("
    "f32[1024,1536]{1,0:T(8,128)S(1)} %bitcast.661, f32[1536,256]{1,0:T(8,"
    "128)S(1)} %negate_select_fusion.15), custom_call_target=\"tpu_custom_"
    "call\", operand_layout_constraints={f32[1024,1536]{1,0}, f32[1536,256]"
    "{1,0}}, frontend_attributes={kernel_metadata={}}",
    "%multiply_convert_fusion.13 = bf16[1024,256]{1,0:T(8,128)(2,1)S(1)} "
    "fusion(f32[1024,256]{1,0:T(8,128)S(1)} %grmac_matmul_pallas.93, f32[]"
    "{:T(128)S(6)} %mul.1196), kind=kLoop, calls=%fused_computation.306."
    "clone.clone",
    "%fusion.322 = (f32[]{:T(128)}, bf16[8,2,128,6,128]{4,2,3,1,0:T(8,128)"
    "(2,1)S(1)}) fusion(bf16[28,1536,256]{2,1,0:T(8,128)(2,1)} "
    "%get-tuple-element.868), kind=kOutput, calls=%fused_computation.220",
]


def test_weight_preparation_follows_the_operands_of_tpu_op_texts():
    short = [program_trace.short_hlo(t) for t in TPU_OPS]
    assert short[1] == ("%fusion.312 = f32[] fusion(bf16[28,1536,256] "
                        "%get-tuple-element.868, s32[] %get-tuple-element.839)")
    assert short[3] == TPU_OPS[3]           # a launch keeps its whole text
    assert program_trace.operands(short[2]) == [
        ("f32[]", "%max.756"), ("bf16[28,1536,256]", "%get-tuple-element.868"),
        ("s32[]", "%get-tuple-element.839")]
    assert [program_trace.makes_scalar(t) for t in short] == [
        True, True, False, False, False, False]
    ops = [trace.Op(trace.compact(t), i, 1) for i, t in enumerate(TPU_OPS)]
    texts = {program_trace.op_head(trace.Op(t, 0, 0)): t for t in short}
    # the re-quantisation and the absmax of the same weight; not the
    # activations' absmax, nor a tuple-making fusion that reads the weight
    assert program_trace.prep_heads(ops, texts.get) == {
        "%negate_select_fusion.15", "%fusion.312"}
    # with no texts only the launch's named weight operand is known
    assert program_trace.prep_heads(ops, {}.get) == {
        "%negate_select_fusion.15"}


def test_program_spans_are_read_from_the_trace_file(tmp_path):
    """Spans come back with their arguments, the harness's and the
    program's only; a file that is not this run's (here a CPU trace, with no
    chip program runs) is not read as the run's."""
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("repro.scheduler.step"):
            with jax.profiler.TraceAnnotation("repro.scheduler.admit", rid=3):
                pass
        with jax.profiler.TraceAnnotation("chipbench.wait_for_arrival"):
            pass
        with jax.profiler.TraceAnnotation("elsewhere"):
            pass
    spans, first, texts = program_trace.load_trace(
        program_trace.newest_xplane(tmp_path))
    assert [s[0] for s in spans] == ["repro.scheduler.step",
                                     "repro.scheduler.admit",
                                     "chipbench.wait_for_arrival"]
    assert spans[1][3] == {"rid": 3} and first is None and texts == {}
    run = _run([h for h in HOST if h[0].startswith("chipbench.")])
    assert program_trace.program_spans(run, tmp_path) == []


@pytest.fixture(scope="module")
def chip_run():
    """The first program runs of a traced chat window of the change on a
    TPU v5e, with the program's host spans (``program_trace --excerpt``)."""
    data = json.loads(gzip.decompress(
        (BENCH / "testdata" / "chat_trace_sites_tpu_v5e.json.gz")
        .read_bytes()))
    devices = trace.from_json(data)
    names = {m[0].split("(", 1)[0] for m in devices[0].modules}
    programs = {"prefill": sorted(n for n in names
                                  if n.startswith("jit_prefill_b")),
                "decode": sorted(n for n in names
                                 if n.startswith("jit_decode_"))}
    # the excerpt's prefill dispatches as the harness logged them, from the
    # arguments of the chip's own prefill-chunk spans
    events = [_prefill(a["slot"], a["tokens"], a["bucket"])
              for _, _, _, a in (h for h in devices[0].host
                                 if h[0] == CHUNK)]
    return trace.RunData(devices, window_s=1.0, programs=programs,
                         events=events, spec=SPEC, peaks=PEAKS, chips=1)


def test_every_launch_on_the_chip_has_a_site(chip_run):
    ops = chip_run.devices[0].ops
    launches = [o for o in ops if trace.is_kernel(o)]
    by_site = program_trace.kernel_ns_by_site(ops)
    assert set(by_site) == {"attn_qkv", "attn_o", "mlp", "head"}
    assert sum(by_site.values()) == sum(o.dur for o in launches)
    # per program run: 28 layers x (q, k, v | o | up, gate, down) + head
    for _, _, _, run_ops in program_trace.runs_with_ops(
            chip_run, ("prefill", "decode")):
        sites = [program_trace.kernel_site(o) for o in run_ops
                 if trace.is_kernel(o)]
        assert len(sites) == 28 * 7 + 1 and sites.count("head") == 1


def test_the_new_readers_read_the_chip_excerpt(chip_run):
    got = {name: harness.metric_reader(name)(chip_run) for name in NEW}
    assert all(v is not None for v in got.values()), got
    assert 0 < got["cim_prep_share_pct.chat"] < 100
    # chunks of 128 and 21 prompt tokens in buckets 128 and 32, 8 lanes
    assert got["prefill_row_use_pct.chat"] == pytest.approx(
        100 * (128 + 21) / (8 * (128 + 32)))
    assert got["head_ms_per_prefill.chat"] > 0
    assert got["host_idle_ms_per_step.chat"] > 0


def test_weight_preparation_on_the_chip_counts_each_weights_absmax(
        chip_run):
    texts = program_trace.op_texts(chip_run)
    for m, _, _, ops in program_trace.runs_with_ops(
            chip_run, ("prefill", "decode")):
        heads = program_trace.prep_heads(ops, texts[m].get)
        made = {program_trace.kernel_weight(o) for o in ops
                if trace.is_kernel(o)}
        scalars = {h for h in heads if program_trace.makes_scalar(texts[m][h])}
        # q, k, v, o, up, gate and down in the layer loop's body, and the head
        assert made <= heads and len(made) == len(scalars) == 8


def test_program_modules_names_the_serving_programs():
    from repro.models import init_params
    from repro.serving.engine import Engine, ServeConfig

    from chipbench import serve
    from tinycell import tiny_cell
    _, cell = tiny_cell()
    arch = harness.arch_from_spec(cell["spec"])
    eng = Engine(arch, init_params(jax.random.PRNGKey(0), arch),
                 ServeConfig(batch_slots=4, max_ctx=128))
    assert serve.program_modules(eng, [8, 16]) == {
        "prefill": ["jit_prefill_b16", "jit_prefill_b8"],
        "decode": ["jit_decode_greedy"]}
