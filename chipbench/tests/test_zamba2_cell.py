"""The zamba2-7b cell's own files: its names resolve to new files, its
configuration holds the program's registered widths, its plain reference
agrees with the program in float32 at the smoke size, and its operation
count is tied to the program's parameters and to XLA's cost analysis."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from chipbench import flops_hybrid
from chipbench import harness as H
from chipbench import weights as W
from chipbench.reference import zamba2
from chipbench.reference.numerics import F32

CELL = "zamba2-7b.train.fsdp4"
SEED = 2 ** 33 + 23
NEW_FILES = ("configs/zamba2-7b.json", "traffic/train_carousel_fsdp4.json",
             "limits/zamba2-7b.train.fsdp4.json",
             "drivers/train_carousel_sharded.py",
             "metrics/train.shared_block_ms.py",
             "metrics/train.collective_ms.py")


def test_cell_resolves_to_its_files():
    c = H.resolve(CELL)
    assert c.workload["chips"] == 4 and c.traffic["mesh"] == [4, 1]
    assert H.driver(c.traffic).run
    for f in NEW_FILES:
        assert (H.BENCH_DIR / f).is_file(), f
    assert {"train.shared_block_ms", "train.collective_ms"} <= {
        m["name"] for m in c.per_layer}


def test_configuration_is_the_programs_at_published_widths():
    """Every model value of the file is the registered zamba2-7b's, but
    the depth and the hybrid layers kept; the catalog's keys agree."""
    from repro.configs.base import get_config
    conf = H.resolve(CELL).config
    cfg = get_config(conf["program_arch"])
    cut = {"num_layers", "hybrid_layer_ids"}
    for k, v in conf["model"].items():
        if k not in cut | {"param_dtype"}:
            assert getattr(cfg, k) == pytest.approx(v), k
    m = conf["model"]
    assert (conf["num_hidden_layers"], conf["hybrid_layer_ids"]) == (
        m["num_layers"], m["hybrid_layer_ids"]) == (12, [5, 11])
    assert [i for i, t in enumerate(conf["layers_block_type"])
            if t == "hybrid"] == [5, 11]
    assert (conf["hidden_size"], conf["mamba_ngroups"],
            conf["attention_head_dim"], conf["attention_hidden_size"],
            conf["adapter_rank"], conf["ffn_hidden_size"]) == (
        m["d_model"], m["ssm_groups"], m["head_dim"],
        m["attn_input_width"], m["adapter_rank"], m["d_ff"])


def _smoke():
    from repro.configs.base import get_smoke_config
    cfg = get_smoke_config("zamba2-7b")
    m = dict(dataclasses.asdict(cfg), param_dtype="bfloat16")
    m["hybrid_layer_ids"] = list(m["hybrid_layer_ids"])
    return cfg, m


def test_reference_matches_program_in_f32():
    from repro.configs.base import RunConfig
    from repro.models import layers as L
    from repro.models import registry
    cfg, m = _smoke()
    spec = zamba2.param_spec(m)
    W.check_layout(spec, W.flatten(registry.param_defs(cfg)))
    p32 = {k: v.astype(jnp.float32)
           for k, v in W.make_params(spec, SEED).items()}
    toks = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 2,
                              cfg.vocab_size)
    run = RunConfig(remat="none")
    with jax.default_matmul_precision("highest"):
        tree = W.nest(p32)
        prog = L.logits_out(tree["embed"], cfg, run,
                            registry.forward(tree, cfg, run,
                                             {"tokens": toks}))
        want = jnp.stack([zamba2.logits(p32, toks[r], m, F32)
                          for r in range(2)])
    assert float(jnp.max(jnp.abs(prog - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))


def test_param_count_matches_program():
    """At the cell's widths: 12 Mamba2 layers, two shared blocks, two
    adapters and linear maps and the tied embedding, 1.758 B."""
    from repro.models import params as P
    from repro.models import registry
    conf = H.resolve(CELL).config
    m = conf["model"]
    cfg = H.program_config(conf)
    n = P.param_count(registry.param_defs(cfg))
    assert n == sum(int(jnp.prod(jnp.array(s)))
                    for _, s, _, _ in zamba2.param_spec(m))
    matmul = (m["num_layers"] * flops_hybrid.mamba_matmul_params(m)
              + len(m["hybrid_layer_ids"])
              * flops_hybrid.shared_matmul_params(m)
              + m["vocab_size"] * m["d_model"])
    # two hybrid layers on two shared blocks: each block's products are
    # counted once, as its weights are; vectors and convolutions are the
    # rest
    assert len(m["hybrid_layer_ids"]) == m["num_mem_blocks"]
    assert 0.999 * n < matmul <= n
    assert 1.75e9 < n < 1.77e9


@pytest.mark.parametrize("T", [64, 128])
def test_forward_count_against_cost_analysis(T):
    """XLA also counts elementwise work and the masked half of causal
    attention and of each SSD chunk, so it reads more at these tiny
    widths; never less."""
    from repro.configs.base import RunConfig
    from repro.models import params as P
    from repro.models import registry
    cfg, m = _smoke()
    run = RunConfig(remat="none", scan_layers=False)
    toks = jax.ShapeDtypeStruct((2, T), jnp.int32)
    fwd = jax.jit(lambda p, t: registry.forward(p, cfg, run,
                                                {"tokens": t}))
    xla = fwd.lower(P.abstract(registry.param_defs(cfg)),
                    toks).compile().cost_analysis()["flops"]
    ours = 2 * T * flops_hybrid.forward_flops(m, T, logits=False)
    assert ours <= xla <= 1.6 * ours


def test_train_count():
    m = H.resolve(CELL).config["model"]
    per_tok = flops_hybrid.train_flops_per_token(m, 2048)
    assert per_tok == 3 * flops_hybrid.forward_flops(m, 2048)
    assert 10e9 < per_tok < 12e9
