"""The frozen yardstick: the jaxpr walker on operations counted by hand, and
on both configurations (abstract evaluation only: nothing runs, nothing
compiles)."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench_testlib import REPO, manifest
from benchmarks import flops


def test_walker_counts_by_hand():
    def f(x, w, k):
        y = jnp.einsum("bsd,df->bsf", x, w)                       # 2*4*5*8*16
        img = jax.lax.conv_general_dilated(                        # 2*(2*6*6*12)*(3*3*4)
            k["img"], k["kernel"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        dw = jax.lax.conv_general_dilated(                         # depthwise: 2*(2*6*6*4)*(3*3*1)
            k["img"], k["dw"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=4)
        body = lambda c, _: (c @ w[:, :8], None)                    # scan x3 of 2*4*8*8 ... on (4, 8)
        c, _ = jax.lax.scan(body, x[:, 0], None, length=3)
        return y.sum() + img.sum() + dw.sum() + c.sum()

    s = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(f)(
        s((4, 5, 8), jnp.float32), s((8, 16), jnp.float32),
        {"img": s((2, 6, 6, 4), jnp.float32), "kernel": s((3, 3, 4, 12), jnp.float32),
         "dw": s((3, 3, 1, 4), jnp.float32)})
    want = 2 * 4 * 5 * 8 * 16 + 2 * (2 * 6 * 6 * 12) * 36 + 2 * (2 * 6 * 6 * 4) * 9 + 3 * 2 * 4 * 8 * 8
    assert flops.jaxpr_flops(jaxpr.jaxpr) == want


@pytest.mark.parametrize("config", manifest()["configs"], ids=lambda c: c["name"])
def test_frozen_counts_are_the_walkers(config):
    with open(os.path.join(REPO, config["file"])) as f:
        cf = json.load(f)
    y = flops.yardstick(cf)
    assert y["flops_per_sample"] == pytest.approx(cf["flops_per_sample"], rel=1e-9)
    assert y["min_bytes_per_step"] == pytest.approx(cf["min_bytes_per_step"], rel=1e-9)
