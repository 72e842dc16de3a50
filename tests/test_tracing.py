"""Names the profiler sees: the named scopes of the train step in the
compiled program's ``op_name`` metadata, and the host spans of the
training loop and the carousel's consumer in a profiler trace."""
import glob
import re
import time

import jax
import pytest

from repro.configs.base import RunConfig, ShapeConfig, get_smoke_config
from repro.kernels.ops import SCOPES
from repro.launch.train import make_carousel_pipeline, run_training
from repro.models import registry
from repro.train.step import init_state, make_train_step

_JIT = re.compile(r"\bp?jit\([^()]*\)")


def scopes_in(op_name: str):
    """The scope names in an ``op_name`` path, outermost first."""
    names = re.split(r"[/()]", _JIT.sub("", op_name))
    return [n for n in names if n in SCOPES]


@pytest.fixture(scope="module")
def step_hlo():
    """The mamba2-130m smoke train step as the CPU compiles it: full
    remat over scanned layers, blockwise cross entropy."""
    cfg = get_smoke_config("mamba2-130m")
    run = RunConfig(remat="full", scan_layers=True, ce_mode="blockwise",
                    ce_block_v=64, warmup_steps=2, total_steps=10)
    key = jax.random.PRNGKey(0)
    state = jax.eval_shape(lambda k: init_state(k, cfg, run), key)
    batch = jax.eval_shape(lambda k: registry.synth_inputs(
        k, cfg, ShapeConfig("t", 32, 2, "train"), "train"), key)
    return jax.jit(make_train_step(cfg, run)).lower(
        state, batch).compile().as_text()


# where each scope of the mamba2 step runs: forward, backward, and the
# recomputed forward pass of the rematerialised blocks
MAMBA2_SCOPES = {
    "embed": ("fwd", "bwd"), "norm": ("fwd", "bwd", "recompute"),
    "proj": ("fwd", "bwd", "recompute"), "conv": ("fwd", "bwd", "recompute"),
    "ssd": ("fwd", "bwd", "recompute"), "logits_ce": ("fwd", "bwd"),
    "adamw": ("fwd",),
}


def _part(op_name: str) -> str:
    if "rematted_computation" in op_name:
        return "recompute"
    return "bwd" if "transpose(" in op_name else "fwd"


@pytest.mark.parametrize("scope", sorted(MAMBA2_SCOPES))
def test_train_step_scope_in_every_pass(step_hlo, scope):
    parts = {_part(n) for n in re.findall(r'op_name="([^"]*)"', step_hlo)
             if scopes_in(n)[-1:] == [scope]}
    assert parts == set(MAMBA2_SCOPES[scope])


def test_every_named_matmul_and_convolution_is_scoped(step_hlo):
    """The CPU compiler makes some dots of its own, with no metadata; each
    one the program named carries a scope (``test_tpu_compile.py`` holds
    the chip's program to every one)."""
    named = [m.group(1) for m in (
        re.search(r'op_name="([^"]*)"', ln) for ln in step_hlo.splitlines()
        if re.search(r" (dot|convolution)\(", ln)) if m]
    assert named
    assert [n for n in named if not scopes_in(n)] == []


def _host_spans(trace_dir, prefixes):
    from jax.profiler import ProfileData
    path = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events
                          if e.name.startswith(prefixes)]
    return sorted(spans)


LOOP = ["train.next_batch", "train.device_put", "train.dispatch",
        "train.loss_read", "train.on_step", "train.checkpoint"]


def test_training_loop_spans_in_loop_order(tmp_path):
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        res = run_training("mamba2-130m", smoke=True, steps=2, seq_len=32,
                           global_batch=2, carousel=True,
                           out_dir=str(tmp_path / "run"), ckpt_every=1,
                           on_step=lambda done, info: None)
    finally:
        jax.profiler.stop_trace()
    assert res["steps"] == 2
    spans = _host_spans(tmp_path / "trace", ("train", "carousel."))
    train = [n for _, _, n in spans if n.startswith("train.")]
    # two steps, the pull that ends the loop, and the final save
    assert train == LOOP * 2 + ["train.next_batch", "train.checkpoint"]
    steps = [(s, e) for s, e, n in spans if n == "train"]
    assert len(steps) == 3
    assert "carousel.assemble" in {n for _, _, n in spans}


def test_carousel_spans_never_span_a_yield(tmp_path):
    """The consumer's waits for a shard and its batch assembly are spans
    that close before each batch is handed over."""
    cfg = get_smoke_config("mamba2-130m")
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        stager, delivery = make_carousel_pipeline(
            cfg, seq_len=32, batch_rows=2, n_shards=3, tape_latency=0.05,
            drives=1, fault_rate=0.0)
        for _ in delivery:
            with jax.profiler.TraceAnnotation("consume"):
                time.sleep(0.001)
        stager.shutdown()
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path / "trace", ("carousel.", "consume"))
    names = {n for _, _, n in spans}
    assert {"carousel.shard_wait", "carousel.assemble", "consume"} <= names
    for s, e, n in spans:
        if n.startswith("carousel."):
            assert not any(s <= cs and ce <= e
                           for cs, ce, cn in spans if cn == "consume"), n
