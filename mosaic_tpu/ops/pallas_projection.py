"""Pallas TPU kernel: H3 lattice projection (the PIP join's front end).

The dense-window join is three stages: projection (pure arithmetic),
entry-table gather, chip-pool gather + parity.  The gathers are XLA's
job (TPU gather issue rate is the constraint, not fusion); the
projection is the hot arithmetic stage — ~200 f32 ops/point of
double-single (df) chains ending in cube rounding — and is exactly the
shape Pallas wants: one VMEM-resident elementwise pass, no HBM round
trips between the trig, the 20-face selection and the rounding.

Face tables are baked into the kernel as python-float constants and the
20-face argmax/selection is an unrolled select chain — no gathers, no
dynamic shapes, every op in the Mosaic-supported set.

df arithmetic here is BARRIER-FREE: ops/twofloat.py pins intermediates
with optimization_barrier to survive XLA:CPU's fma contraction, but
inside a Pallas kernel the Mosaic compiler lowers ops 1:1 (no
contraction pass), and optimization_barrier is not lowerable — so the
kernel carries its own plain Dekker helpers.  Consequence: the
interpret-mode (CPU) tests only check structural agreement with the
reference path; the full precision contract is asserted on real TPU in
tests_tpu/.

Status: opt-in (MOSAIC_PIP_PALLAS=1 routes the dense join's projection
through this kernel) until validated on hardware; semantics are pinned
by tests either way.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.index.h3.constants import M_SIN60
from ..core.index.h3.hexmath import face_center_xyz, scaled_bases

#: one grid step is a (_SUBLANES, _LANES) f32 block: the TPU compiler
#: needs the last two block dims to be multiples of the (8, 128) tile
_SUBLANES = 8
_LANES = 1024


# ---------------------------------------------- barrier-free df helpers

def _two_sum(a, b):
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _fast_two_sum(a, b):
    s = a + b
    return s, b - (s - a)


def _two_prod(a, b):
    split = jnp.float32(4097.0)
    p = a * b
    ca = split * a
    ahi = ca - (ca - a)
    alo = a - ahi
    cb = split * b
    bhi = cb - (cb - b)
    blo = b - bhi
    err = ((ahi * bhi - p) + ahi * blo + alo * bhi) + alo * blo
    return p, err


def _df_add(x, y):
    s, e = _two_sum(x[0], y[0])
    e = e + (x[1] + y[1])
    return _fast_two_sum(s, e)


def _df_sub(x, y):
    return _df_add(x, (-y[0], -y[1]))


def _df_mul(x, y):
    p, e = _two_prod(x[0], y[0])
    e = e + (x[0] * y[1] + x[1] * y[0])
    return _fast_two_sum(p, e)


def _df_div(x, y):
    q1 = x[0] / y[0]
    r = _df_sub(x, _df_mul(y, (q1, jnp.float32(0.0))))
    q2 = (r[0] + r[1]) / y[0]
    return _fast_two_sum(q1, q2)


def _df_const(v: float):
    hi = np.float32(v)
    lo = np.float32(np.float64(v) - np.float64(hi))
    return (jnp.float32(hi), jnp.float32(lo))


def _df_poly_sin(d):
    d2 = _df_mul(d, d)
    t = _df_sub(_df_const(1.0), (d2[0] * np.float32(1 / 20.0),
                                 d2[1] * np.float32(1 / 20.0)))
    t = _df_sub(_df_const(1.0),
                _df_mul((d2[0] * np.float32(1 / 6.0),
                         d2[1] * np.float32(1 / 6.0)), t))
    return _df_mul(d, t)


def _df_poly_cos(d):
    d2 = _df_mul(d, d)
    t = _df_sub(_df_const(1.0), (d2[0] * np.float32(1 / 30.0),
                                 d2[1] * np.float32(1 / 30.0)))
    t = _df_sub(_df_const(1.0),
                _df_mul((d2[0] * np.float32(1 / 12.0),
                         d2[1] * np.float32(1 / 12.0)), t))
    return _df_sub(_df_const(1.0),
                   _df_mul((d2[0] * np.float32(0.5),
                            d2[1] * np.float32(0.5)), t))


def _trig_local(d_deg, origin_deg: float):
    rad = _df_mul((d_deg, jnp.float32(0.0)), _df_const(math.pi / 180.0))
    s_d = _df_poly_sin(rad)
    c_d = _df_poly_cos(rad)
    o = math.radians(origin_deg)
    s0 = _df_const(math.sin(o))
    c0 = _df_const(math.cos(o))
    sin = _df_add(_df_mul(s0, c_d), _df_mul(c0, s_d))
    cos = _df_sub(_df_mul(c0, c_d), _df_mul(s0, s_d))
    return sin, cos


def _make_kernel(res: int, origin: Tuple[float, float]):
    f_xyz = face_center_xyz()                          # [20, 3] f64
    e1, e2 = scaled_bases(res)
    tables = np.concatenate([f_xyz, e1, e2], axis=1)   # [20, 9]
    t_hi = tables.astype(np.float32)
    t_lo = (tables - t_hi.astype(np.float64)).astype(np.float32)
    lon0, lat0 = origin

    def kernel(x_ref, y_ref, face_ref, a_ref, b_ref, m_ref, g_ref):
        x = x_ref[...]
        y = y_ref[...]
        sin_lat, cos_lat = _trig_local(y, lat0)
        sin_lng, cos_lng = _trig_local(x, lon0)
        X = _df_mul(cos_lat, cos_lng)
        Y = _df_mul(cos_lat, sin_lng)
        Z = sin_lat

        # 20-face argmax on hi parts (unrolled)
        best = jnp.full_like(x, -2.0)
        second = jnp.full_like(x, -2.0)
        face = jnp.zeros_like(x, dtype=jnp.int32)
        for f in range(20):
            d = (X[0] * np.float32(f_xyz[f, 0]) +
                 Y[0] * np.float32(f_xyz[f, 1]) +
                 Z[0] * np.float32(f_xyz[f, 2]))
            better = d > best
            second = jnp.where(better, best, jnp.maximum(second, d))
            face = jnp.where(better, jnp.int32(f), face)
            best = jnp.where(better, d, best)
        gap = best - second

        # per-face basis selection (unrolled selects, exact)
        sel = [(jnp.zeros_like(x), jnp.zeros_like(x)) for _ in range(9)]
        for f in range(20):
            hit = face == f
            for k in range(9):
                sel[k] = (jnp.where(hit, np.float32(t_hi[f, k]),
                                    sel[k][0]),
                          jnp.where(hit, np.float32(t_lo[f, k]),
                                    sel[k][1]))

        def dot3(k):
            acc = _df_mul(X, sel[k])
            acc = _df_add(acc, _df_mul(Y, sel[k + 1]))
            return _df_add(acc, _df_mul(Z, sel[k + 2]))

        u = dot3(0)
        px = _df_div(dot3(3), u)
        py = _df_div(dot3(6), u)

        rf = _df_mul(py, _df_const(1.0 / M_SIN60))
        qf = _df_sub(px, (rf[0] * np.float32(0.5),
                          rf[1] * np.float32(0.5)))
        sf = _df_sub((-qf[0], -qf[1]), rf)

        def df_round(v):
            r = jnp.round(v[0])
            frac = (v[0] - r) + v[1]
            one, zero = np.float32(1.0), np.float32(0.0)
            adj = jnp.where(frac > 0.5, one, zero) - \
                jnp.where(frac < -0.5, one, zero)
            return r + adj, frac - adj

        rq, fq = df_round(qf)
        rr, fr = df_round(rf)
        rs, fs = df_round(sf)
        dq = jnp.abs(fq)
        dr = jnp.abs(fr)
        ds = jnp.abs(fs)
        fix_q = (dq > dr) & (dq > ds)
        fix_r = (~fix_q) & (dr > ds)
        rq2 = jnp.where(fix_q, -rr - rs, rq)
        rr2 = jnp.where(fix_r, -rq2 - rs, rr)
        fq = fq + (rq - rq2)
        fr = fr + (rr - rr2)

        vx = fq + np.float32(0.5) * fr
        vy = np.float32(M_SIN60) * fr
        h = np.float32(0.5) * vx
        sv = np.float32(M_SIN60) * vy
        proj = jnp.maximum(jnp.abs(vx),
                           jnp.maximum(jnp.abs(h + sv),
                                       jnp.abs(h - sv)))
        face_ref[...] = face
        a_ref[...] = (rq2 + rr2).astype(jnp.int32)
        b_ref[...] = rr2.astype(jnp.int32)
        m_ref[...] = jnp.maximum(np.float32(0.5) - proj,
                                 np.float32(0.0))
        g_ref[...] = gap

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("res", "origin", "interpret"))
def project_lattice_pallas(xy_local: jnp.ndarray, res: int,
                           origin: Tuple[float, float],
                           interpret: bool = False):
    """Pallas version of jaxkernel._project_df (df path, localized
    input): [N, 2] local degrees -> (face, a, b, margin_lattice,
    facegap).  N is padded internally to whole (8, 1024) blocks."""
    from jax.experimental import pallas as pl

    n = xy_local.shape[0]
    rows = _SUBLANES * -(-max(n, 1) // (_SUBLANES * _LANES))
    pad = rows * _LANES - n
    x = jnp.pad(xy_local[:, 0].astype(jnp.float32), (0, pad))
    y = jnp.pad(xy_local[:, 1].astype(jnp.float32), (0, pad))
    x = x.reshape(rows, _LANES)
    y = y.reshape(rows, _LANES)
    kernel = _make_kernel(res, origin)
    # int32 block indices: the package runs with x64 on, where a bare
    # 0 would trace as int64, which the TPU lowering refuses
    spec = pl.BlockSpec((_SUBLANES, _LANES),
                        lambda i: (i, jnp.int32(0)))
    out = pl.pallas_call(
        kernel,
        grid=(rows // _SUBLANES,),
        in_specs=[spec, spec],
        out_specs=[spec] * 5,
        out_shape=[
            jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(x, y)
    face, a, b, margin, gap = [o.reshape(-1)[:n] for o in out]
    return face, a, b, margin, gap
