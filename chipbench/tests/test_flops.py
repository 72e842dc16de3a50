"""Operation counts from shapes, tied to the program's parameters and to
XLA's cost analysis of a small forward pass without remat."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from chipbench import flops
from chipbench import harness as H
from chipbench.reference import mamba2


def _config():
    return json.loads((H.BENCH_DIR / "configs" / "mamba2-130m.json")
                      .read_text())


def test_param_counts_match_program():
    """The source's 50277 ids padded to 50288 rows: 129.0M parameters."""
    from repro.models import params as P
    from repro.models import registry
    conf = _config()
    spec = mamba2.param_spec(conf["model"])
    cfg = H.program_config(conf)
    assert flops.param_count(spec) == P.param_count(
        registry.param_defs(cfg)) == 128_989_632


def test_matmul_params_cover_the_model():
    """The matrix-product parameters are the model less its vectors and
    convolutions."""
    m = _config()["model"]
    per_token = (m["num_layers"] * flops.mamba_matmul_params(m)
                 + m["vocab_size"] * m["d_model"])
    model = flops.param_count(mamba2.param_spec(m))
    assert 0.99 * model < per_token <= model


@pytest.mark.parametrize("T", [128, 256])
def test_forward_count_against_cost_analysis(T):
    """XLA also counts elementwise work (norms, activations, the SSD's
    decays), so it reads up to a third more at these tiny widths; never
    less."""
    from repro.configs.base import RunConfig, get_smoke_config
    from repro.models import params as P
    from repro.models import registry
    cfg = get_smoke_config("mamba2-130m")
    m = dataclasses.asdict(cfg)
    run = RunConfig(remat="none", scan_layers=False)
    toks = jax.ShapeDtypeStruct((2, T), jnp.int32)
    fwd = jax.jit(lambda p, t: registry.forward(p, cfg, run,
                                                {"tokens": t}))
    xla = fwd.lower(P.abstract(registry.param_defs(cfg)),
                    toks).compile().cost_analysis()["flops"]
    ours = 2 * T * flops.forward_flops(m, logits=False)
    assert ours <= xla <= 1.35 * ours


def test_train_count():
    m = _config()["model"]
    per_tok = flops.train_flops_per_token(m)
    assert per_tok == 3 * flops.forward_flops(m)
    assert 6 * 128.99e6 < per_tok < 6 * 128.99e6 * 1.2
