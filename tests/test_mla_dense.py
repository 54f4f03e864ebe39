"""`mla_attention` without a selection (ISSUE 47): a prompt plainly causal
over expanded keys and values, a chunk's queries against the latents of
every earlier position, a step on the absorbed path over the slot's rows
where they lie; against the equations written out in numpy. The paths with
a selection keep their lowering."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.ops import LOWERINGS

HEADS, RANK, NOPE, ROPE, VD, WIDTH = 3, 16, 12, 4, 8, 128
RNG = np.random.default_rng(5)


def lower(ins, **attrs):
    ins = {k: [jnp.asarray(v)] for k, v in ins.items() if v is not None}
    return np.asarray(LOWERINGS["mla_attention"](
        None, ins, dict(heads=HEADS, nope_dim=NOPE, rope_dim=ROPE, v_dim=VD,
                        **attrs))["Out"][0])


def operands(tq, tk, b=1):
    q = RNG.normal(size=(b, tq, HEADS * (NOPE + ROPE))).astype(np.float32)
    lat = np.zeros((b, tk, WIDTH), np.float32)
    lat[..., :RANK + ROPE] = RNG.normal(size=(b, tk, RANK + ROPE))
    wuk = RNG.normal(size=(RANK, HEADS * NOPE)).astype(np.float32) / 4
    wuv = RNG.normal(size=(RANK, HEADS * VD)).astype(np.float32) / 4
    return q, lat, wuk, wuv


def written_out(q, lat, wuk, wuv, rows):
    """Expanded attention of query i at row rows[i] over lat's rows <= it."""
    tq, tk = q.shape[0], lat.shape[0]
    qh = q.reshape(tq, HEADS, NOPE + ROPE).astype(np.float64)
    ckv, kr = lat[:, :RANK].astype(np.float64), lat[:, RANK:RANK + ROPE]
    k = np.concatenate([(ckv @ wuk).reshape(tk, HEADS, NOPE), np.broadcast_to(
        kr[:, None, :], (tk, HEADS, ROPE))], -1)
    v = (ckv @ wuv).reshape(tk, HEADS, VD)
    s = np.einsum("qhd,khd->hqk", qh, k) * (NOPE + ROPE) ** -0.5
    s = np.where(np.arange(tk)[None, None, :] <= np.asarray(rows)[None, :,
                                                                  None],
                 s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hqk,khd->qhd", p, v).reshape(tq, HEADS * VD)


def test_a_prompt_is_plainly_causal():
    q, lat, wuk, wuv = operands(10, 10)
    before = obs.counter("ops.mla_attention.causal_blocks")
    got = lower({"Q": q, "Latent": lat, "Wuk": wuk, "Wuv": wuv})
    assert obs.counter("ops.mla_attention.causal_blocks") == before + 1
    np.testing.assert_allclose(
        got[0], written_out(q[0], lat[0], wuk, wuv, np.arange(10)),
        atol=2e-5)


@pytest.mark.parametrize("tq,offset", [(4, 9), (128, 64), (256, 0)])
def test_a_chunks_queries_go_against_every_earlier_latent(tq, offset):
    tk = offset + tq + 7
    q, lat, wuk, wuv = operands(tq, tk)
    got = lower({"Q": q, "Latent": lat, "Wuk": wuk, "Wuv": wuv,
                 "Offset": np.asarray([[offset]], np.int64)})
    np.testing.assert_allclose(
        got[0], written_out(q[0], lat[0], wuk, wuv, offset + np.arange(tq)),
        atol=5e-5)


def test_the_absorbed_step_equals_the_expanded_form():
    """One query a slot over the slot's whole cache where it lies, rows <=
    pos: no gather, the same numbers as the expanded form; a selection of
    every row up to pos gives the same too."""
    b, tk = 3, 24
    q, lat, wuk, wuv = operands(1, tk, b)
    pos = np.asarray([[5], [23], [0]], np.int64)
    before = obs.counter("ops.mla_attention.dense_step")
    got = lower({"Q": q, "Latent": lat, "Wuk": wuk, "Wuv": wuv, "Pos": pos})
    assert obs.counter("ops.mla_attention.dense_step") == before + 1
    sel = np.where(np.arange(tk)[None, :] <= pos, np.arange(tk)[None, :],
                   -1).astype(np.int32)
    kept = lower({"Q": q, "Latent": lat, "Wuk": wuk, "Wuv": wuv, "Pos": pos,
                  "Selected": sel})
    for i in range(b):
        want = written_out(q[i], lat[i], wuk, wuv, [int(pos[i, 0])])
        np.testing.assert_allclose(got[i], want, atol=2e-5)
        np.testing.assert_allclose(kept[i], want, atol=2e-5)


def test_a_chunk_takes_no_selection():
    q, lat, wuk, wuv = operands(4, 4)
    with pytest.raises(ValueError, match="takes no selection"):
        lower({"Q": q, "Latent": lat, "Wuk": wuk, "Wuv": wuv,
               "Selected": np.ones((1, 4, 4), np.int8),
               "Offset": np.asarray([[0]], np.int64)})
