"""Trip-count-aware HLO accounting tests (the roofline's data source)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_accounting as ha
from repro.launch import analysis


def _compile(f, *shapes):
    return jax.jit(f).lower(*[jax.ShapeDtypeStruct(s, jnp.float32)
                              for s in shapes]).compile()


W = jnp.ones((128, 128))


def test_scan_body_multiplied_by_trip_count():
    def f(x):
        return jax.lax.scan(lambda c, _: (c @ W, None), x, None,
                            length=10)[0]
    acc = ha.account(_compile(f, (128, 128)).as_text())
    assert acc.flops == pytest.approx(2 * 128 ** 3 * 10, rel=0.01)


def test_nested_scans_multiply():
    def f(x):
        def outer(c, _):
            return jax.lax.scan(lambda c2, _: (c2 @ W, None), c, None,
                                length=5)[0], None
        return jax.lax.scan(outer, x, None, length=3)[0]
    acc = ha.account(_compile(f, (128, 128)).as_text())
    assert acc.flops == pytest.approx(2 * 128 ** 3 * 15, rel=0.01)


def test_unrolled_matches_scan():
    def f10(x):
        for _ in range(10):
            x = x @ W
        return x

    def fs(x):
        return jax.lax.scan(lambda c, _: (c @ W, None), x, None,
                            length=10)[0]
    a1 = ha.account(_compile(f10, (128, 128)).as_text())
    a2 = ha.account(_compile(fs, (128, 128)).as_text())
    assert a1.flops == pytest.approx(a2.flops, rel=0.01)


def test_xla_cost_analysis_undercounts_scans():
    """The reason this module exists: document the backend behaviour."""
    def fs(x):
        return jax.lax.scan(lambda c, _: (c @ W, None), x, None,
                            length=10)[0]
    c = _compile(fs, (128, 128))
    xla = c.cost_analysis()["flops"]
    ours = ha.account(c.as_text()).flops
    assert ours > 5 * xla     # 10x body count vs 1x


def test_batched_dot_flops():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)
    acc = ha.account(_compile(f, (4, 64, 32), (4, 32, 16)).as_text())
    assert acc.flops == pytest.approx(2 * 4 * 64 * 32 * 16, rel=0.01)


def test_bytes_positive_and_scaled_by_loop():
    def f1(x):
        return jax.lax.scan(lambda c, _: (c @ W, None), x, None,
                            length=2)[0]

    def f2(x):
        return jax.lax.scan(lambda c, _: (c @ W, None), x, None,
                            length=20)[0]
    b1 = ha.account(_compile(f1, (128, 128)).as_text()).bytes
    b2 = ha.account(_compile(f2, (128, 128)).as_text()).bytes
    assert b2 > 5 * b1


def test_collective_summary_factors():
    ops = [analysis.CollectiveOp("all-reduce", 1000, 4, False),
           analysis.CollectiveOp("all-gather", 1000, 4, True)]
    s = analysis.collective_summary(ops)
    assert s["wire_bytes"] == pytest.approx(2 * 0.75 * 1000 + 0.75 * 1000)
    assert s["wire_bytes_cross_pod"] == pytest.approx(0.75 * 1000)


def test_parse_collectives_literal_groups():
    hlo = ('%ar = f32[512]{0} all-reduce(f32[512]{0} %x), '
           'replica_groups={{0,256},{1,257}}, to_apply=%add\n')
    ops = analysis.parse_collectives(hlo, pod_stride=256)
    assert len(ops) == 1
    assert ops[0].bytes >= 512 * 4
    assert ops[0].group_size == 2
    assert ops[0].crosses_pod is True
