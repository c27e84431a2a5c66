"""What a step counts, per configuration and traffic mix: tokens,
required FLOPs and the STORM kernel's bytes.  The committed FedBiOAcc
cells' numbers are pinned to those the harness gave before the counts
followed the traffic's participation and algorithm; FedBiOAcc-Local's
under 2-of-4 participation to a count by hand."""
import json
import os

import jax
import pytest

from bench import cell as cells
from bench import run as R
from bench import storm_bytes, traffic
from bench.tests.tiny import REPO

#: (configuration, traffic mix): tokens, FLOPs and STORM bytes of one step
PINNED = {
    ("mamba2-130m", "fedbioacc.m2.seq1024"): (4096, 9402455162880.0,
                                              6598848000),
    ("granite-moe-1b-a400m.cut", "fedbioacc.m2.seq1024"): (
        4096, 7705641091072.0, 11681726464),
    ("mamba2-130m", "fedbioacc.m2.seq256"): (1024, 2350613790720.0,
                                             6598848000),
    # 2 of 4 clients x 1 row x 1024 tokens x 2 streams; FLOPs: the seq1024
    # mix's 16 units for 2 clients plus 2 points x 2 Hessian products x 2
    # head products of 2 x 768 x 50280 over 2048 tokens; bytes: x|y of all
    # 4 clients, (bf16 x 4 + f32 x 12) per element (the seq1024 mix's
    # x|y|u for 2 clients less one head, doubled)
    ("mamba2-130m", "fedbioacc_local.m4.u2.seq1024"): (
        4096, 9402455162880.0 + 2048 * 8 * 2 * 768 * 50280,
        2 * (6598848000 - 2 * 768 * 50280 * 16)),
}


def read(*parts):
    with open(os.path.join(REPO, "bench", *parts)) as fh:
        return json.load(fh)


@pytest.mark.parametrize("config,mix", sorted(PINNED))
def test_counts_per_step(config, mix):
    c = cells.Cell("t", 1, read("configs", config + ".json"),
                   read("traffic", mix + ".json"), {}, {})
    fam, _ = R.family(c.config)
    params = jax.eval_shape(
        lambda k: fam.init(k, c.config["sizes"], R.param_dtype(c.config)),
        jax.random.PRNGKey(0))
    tokens, flops, nbytes = PINNED[config, mix]
    assert traffic.tokens_per_step(c.traffic) == tokens
    assert R.flops_per_step(c) == flops
    secs = {"fedbioacc": ("x", "y", "u"),
            "fedbioacc_local": ("x", "y")}[R.algorithm(c.traffic)]
    assert storm_bytes.bytes_per_step(params, c.traffic["clients"],
                                      secs) == nbytes


def test_participants_under_each_sampler():
    t = read("traffic", "fedbioacc_local.m4.u2.seq1024.json")
    assert traffic.participants(t) == 2
    t["experiment"]["participation"]["clients_per_round"] = 0
    assert traffic.participants(t) == 4
    assert traffic.participants(read("traffic",
                                     "fedbioacc.m2.seq1024.json")) == 2
    t["experiment"]["participation"]["sampler"] = "trace"
    with pytest.raises(SystemExit):
        traffic.participants(t)
