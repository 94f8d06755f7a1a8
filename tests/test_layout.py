import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.accelerator import LayoutConfig
from repro.core.layout import (chw_ids, evaluate_layout, flat_ids,
                               slowdown_per_cycle, streaming_access_pattern)


def test_paper_equations_chw():
    cfg = LayoutConfig(enabled=True, c1_step=8, h1_step=2, w1_step=4,
                       num_banks=8, line_bytes=16)
    C, H, W = 16, 8, 8
    c = jnp.arange(C)[:, None, None] * jnp.ones((1, H, W), jnp.int32)
    h = jnp.arange(H)[None, :, None] * jnp.ones((C, 1, W), jnp.int32)
    w = jnp.arange(W)[None, None, :] * jnp.ones((C, H, 1), jnp.int32)
    line, col, bank = chw_ids(c, h, w, H, W, cfg)
    # line id formula at a known point
    c0, h0, w0 = 9, 3, 5
    expect_line = (c0 // 8) * (-(-H // 2)) * (-(-W // 4)) \
        + (h0 // 2) * (-(-W // 4)) + (w0 // 4)
    assert int(line[c0, h0, w0]) == expect_line
    expect_col = (w0 % 4) * 2 * 8 + (h0 % 2) * 8 + (c0 % 8)
    assert int(col[c0, h0, w0]) == expect_col


def test_slowdown_equation():
    # 4 accesses to the same bank, different lines, 1 port -> slowdown 4
    line = jnp.array([[0, 1, 2, 3]])
    bank = jnp.zeros((1, 4), jnp.int32)
    sd = slowdown_per_cycle(line, bank, num_banks=4, ports=1)
    assert int(sd[0]) == 4
    # same line 4x -> one distinct line -> slowdown 1
    sd2 = slowdown_per_cycle(jnp.zeros((1, 4), jnp.int32), bank, 4, 1)
    assert int(sd2[0]) == 1
    # 2 ports halve it
    sd3 = slowdown_per_cycle(line, bank, num_banks=4, ports=2)
    assert int(sd3[0]) == 2


def test_more_banks_fewer_conflicts_fig12():
    """Figs. 12-13: at fixed total bandwidth, more banks -> less slowdown."""
    means = []
    for banks in (2, 4, 8, 16):
        cfg = LayoutConfig(enabled=True, num_banks=banks,
                           line_bytes=512 // banks)
        r = evaluate_layout(cfg, R=32, n_cycles=128, lead_stride=1,
                            elem_stride=197)
        means.append(r.mean_slowdown)
    assert all(means[i] >= means[i + 1] for i in range(len(means) - 1))
    assert means[0] > 2 * means[-1]


def test_contiguous_access_no_slowdown():
    cfg = LayoutConfig(enabled=True, num_banks=32, line_bytes=64)
    # one element per cycle: can never conflict
    r = evaluate_layout(cfg, R=1, n_cycles=64, lead_stride=1, elem_stride=1)
    assert r.mean_slowdown == 1.0


def test_kernel_matches_oracle():
    from repro.kernels.conflict import (conflict_slowdown,
                                        conflict_slowdown_reference)
    key = jax.random.PRNGKey(3)
    line = jax.random.randint(key, (96, 48), 0, 13)
    bank = jax.random.randint(jax.random.fold_in(key, 1), (96, 48), 0, 16)
    k = conflict_slowdown(line, bank, num_banks=16, ports=2, interpret=True)
    r = conflict_slowdown_reference(line, bank, num_banks=16, ports=2)
    np.testing.assert_array_equal(np.asarray(k), np.asarray(r))


# The sweep kernel's layout stage: one slowdown curve per distinct (array
# rows, op stride) of a program, each (design, op) averaging its own row
# over its own compute window.
TABLE_ROWS = (8, 32, 64, 128)
TABLE_STRIDES = (1, 197, 1024, 100003)
# below 8, between 8 and the 512-cycle window (whole and fractional), at
# and above it: n_valid's clip and floor both act
TABLE_COMPS = (3.0, 7.9, 8.0, 8.7, 100.5, 511.2, 512.0, 513.3, 4096.0)


@pytest.mark.parametrize("word_bytes", (1, 2))
@pytest.mark.parametrize("ports", (1, 2))
@pytest.mark.parametrize("banks", (16, 32, 64))
def test_deduplicated_curves_match_per_op_layout_model(banks, ports,
                                                       word_bytes):
    from repro.core.layout import (STREAM_WINDOW_CYCLES,
                                   streaming_layout_extra,
                                   streaming_slowdown_table,
                                   streaming_window_extra)
    cfg = LayoutConfig(enabled=True, num_banks=banks, ports_per_bank=ports)
    ops = [(s, c) for s in range(len(TABLE_STRIDES)) for c in TABLE_COMPS]
    op_stride = jnp.asarray([s for s, _ in ops], jnp.int32)
    comp = jnp.asarray([[c for _, c in ops]] * len(TABLE_ROWS), jnp.float32)

    @jax.jit
    def batched(rows, strides, row_of_design, comp):
        # as the sweep program: the table once, then a gather per design
        table = streaming_slowdown_table(cfg, rows, strides, word_bytes,
                                         r_cap=128)
        extra = jax.vmap(lambda ri, c: streaming_window_extra(
            table[ri][op_stride], c))(row_of_design, comp)
        return table, extra

    table, extra = batched(jnp.asarray(TABLE_ROWS, jnp.int32),
                           jnp.asarray(TABLE_STRIDES, jnp.int32),
                           jnp.arange(len(TABLE_ROWS), dtype=jnp.int32),
                           comp)
    per_op = np.asarray(
        [[float(streaming_layout_extra(cfg, R, c, TABLE_STRIDES[s],
                                       word_bytes)) for s, c in ops]
         for R in TABLE_ROWS], np.float32)
    assert np.array_equal(np.asarray(extra), per_op)
    assert (per_op > 0).any()
    # each curve is the per-cycle slowdown of exactly R rows (the one-hot
    # oracle, no masking): rows past R under r_cap add nothing; each op
    # averages its curve over its first clip(floor(comp), 8, 512) cycles
    f32 = np.float32
    for i, R in enumerate(TABLE_ROWS):
        for j, n in enumerate(TABLE_STRIDES):
            idx = streaming_access_pattern(R, STREAM_WINDOW_CYCLES, 1, n)
            line, _, bank = flat_ids(idx, cfg, word_bytes)
            sd = np.asarray(slowdown_per_cycle(line, bank, banks, ports))
            assert np.array_equal(np.asarray(table[i, j]), sd)
            for k, (s, c) in enumerate(ops):
                if s != j:
                    continue
                n_valid = min(max(int(min(c, STREAM_WINDOW_CYCLES)), 8),
                              STREAM_WINDOW_CYCLES)
                want = ((f32(sd[:n_valid].sum()) / f32(n_valid) - f32(1))
                        * f32(c))
                assert per_op[i, k] == want, (R, n, c)
