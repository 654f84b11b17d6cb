"""Device-side H3 cell assignment (jax: stable vector gnomonic projection
+ exact int32 lattice math).

The reference assigns cells row-at-a-time through JNI
(H3IndexSystem.pointToIndex:168 -> h3.geoToH3); here the whole pipeline is
branch-free tensor math that XLA fuses into one kernel, split in two:

  project_lattice_jax   points -> (face, axial a/b, margin, facegap)
  cell_from_lattice_jax (face, a, b) -> canonical 64-bit cell id

The split matters for the PIP join: its dense-window index addresses
directly off (face, a, b), skipping id encoding entirely
(parallel/pip_join.py).

Precision design (this replaced a polar-form f32 kernel whose arccos
conditioning cost ~3 m of cell-assignment uncertainty):

* The projection is the tangent-basis form x = (P·E1)/(P·F) — no arccos,
  no atan2, no mod; every step is a well-conditioned product/sum
  (hexmath.face_tangent_bases holds the f64 derivation).
* With an ``origin``, inputs are origin-local degrees and the hot path
  runs in double-single f32 (ops/twofloat.py): origin trig enters as
  exact df constants, the small-angle sin/cos are df Taylor polynomials,
  and the three basis dot products + division stay df until cube
  rounding.  Residual error is ~1e-9 cell widths — the margin band
  effectively vanishes and the f64 host recheck set is just the points
  genuinely on a boundary.
* Without an origin inputs are absolute f32 degrees; error is dominated
  by the f32 representation of the coordinates themselves (~1e-5 deg at
  lng ~100).  ERR_LATTICE_* below carry the validated bounds.

Axial-coordinate forms (a, b) = (i - k, j - k) of the aperture-7 steps,
derived from the ijk matrices in hexmath.py:

    plain:  up  a'=round((3a-b)/7), b'=round((a+2b)/7)
            down A=2a+b,  B=-a+3b
    rot:    up  a'=round((2a+b)/7), b'=round((3b-a)/7)
            down A=3a-b,  B=a+2b
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.nn
import jax.numpy as jnp
import numpy as np

from ....ops.twofloat import (DF, df_add, df_const, df_div, df_from_f32,
                             df_mul, df_mul_f32, df_poly_cos, df_poly_sin,
                             df_round, df_sub)
from .constants import M_SIN60, M_SQRT7, RES0_U_GNOMONIC, face_center_xyz
from .hexmath import scaled_bases
from .index import MODE_CELL, _BASE_SHIFT, _MODE_SHIFT, _RES_SHIFT, \
    _digit_shift
from .tables import _down_rot, tables

# axial diff (da+1)*3 + (db+1) -> digit (7 = impossible)
_DIGIT_OF_DIFF = np.array([1, 3, 7, 5, 0, 2, 7, 4, 6], dtype=np.int32)

#: localized inputs must stay within this window for the df Taylor
#: series' error bound (0.04 rad); checked by the PIP index builder.
MAX_LOCAL_DEG = 2.2

#: face-dot gap below which nearest-face selection is ambiguous in f32
#: (flag for host recheck; band is ~1e-7 of the sphere)
FACEGAP_EPS = 1e-6


def pick_precision(precision: str = "auto") -> str:
    """Resolve the projection arithmetic path.

    "f64"  — native float64 (CPU: free and exact; TPU: software-emulated,
             slow).  The test/dryrun path.
    "df"   — double-single f32 (ops/twofloat.py).  The TPU path: TPUs
             have no native f64, and unlike XLA:CPU the TPU compiler
             does not contract/reassociate f32 chains, so the Dekker
             transforms survive (XLA:CPU compiles `t1 - p` into
             fma(ahi, bhi, -p) straight through optimization_barrier,
             collapsing df to plain f32 — measured, which is why "auto"
             never picks df on CPU).
    "f32"  — plain f32 (largest uncertainty band; fallback).
    """
    if precision != "auto":
        return precision
    import jax
    if jax.default_backend() == "tpu":
        return "df"
    # df survival was only measured on the TPU compiler; XLA:CPU (and
    # likely XLA:GPU) contract the Dekker transforms, so every other
    # backend gets native f64 (or plain f32 with its wide margin band)
    return "f64" if jax.config.jax_enable_x64 else "f32"


def err_lattice_bound(res: int, precision: str,
                      max_abs_deg: float = 180.0,
                      localized: bool = True) -> float:
    """Upper bound (lattice units, 1 = cell pitch) on the device
    projection's planar error at ``res`` — the margin threshold below
    which cell assignment must be treated as uncertain.

    Derivation (validated by tools/validate_projection.py; 8x safety):
    * input representation: points arrive f32; an ulp at the coordinate
      magnitude, through radians and the gnomonic scale;
    * arithmetic: ~1e-7 relative (f32 paths), ~1e-13 (df), ~1e-15 (f64)
      of the planar magnitude (~scale * face radius).
    """
    scale = M_SQRT7 ** res / RES0_U_GNOMONIC
    ulp_deg = np.spacing(np.float32(max_abs_deg)) if not localized else \
        np.spacing(np.float32(min(max_abs_deg, MAX_LOCAL_DEG)))
    input_err = float(ulp_deg) * np.pi / 180.0 * scale * 1.3
    planar_mag = scale * RES0_U_GNOMONIC  # ~tan(face radius) * scale
    arith_rel = {"f32": 4e-7, "df": 1e-12, "f64": 1e-15}[precision]
    return 8.0 * (input_err + arith_rel * planar_mag)

_CONSTS = None


def _consts():
    """Numpy-held constants, wrapped to jnp per call so jit traces embed
    them as constants instead of leaking cached tracers."""
    global _CONSTS
    if _CONSTS is None:
        t = tables()
        _CONSTS = {
            "face_xyz": face_center_xyz().astype(np.float32),
            "fijk_base": t.fijk_base.reshape(-1).astype(np.int32),
            "fijk_rot": np.maximum(t.fijk_rot, 0).reshape(-1).astype(
                np.int32),
            "fijk_extra": t.fijk_pent_extra.reshape(-1).astype(np.int32),
            "rot_digit": t.rot_digit.reshape(-1).astype(np.int32),
            "is_pent": t.is_pentagon.astype(np.int32),
            "pent_seam": t.pent_seam.astype(np.int32),
            "digit_of_diff": _DIGIT_OF_DIFF,
        }
    return {k: jnp.asarray(v) for k, v in _CONSTS.items()}


def _round_div7(p):
    """Nearest-integer p/7 for int32 p (ties impossible for integer p)."""
    return jnp.floor_divide(2 * p + 7, 14)


def _basis_table(res: int) -> Tuple[np.ndarray, np.ndarray]:
    """[20, 9] hi/lo f32 tables of (F, E1s, E2s) rows per face."""
    e1, e2 = scaled_bases(res)
    tbl = np.concatenate([face_center_xyz(), e1, e2], axis=-1)  # [20, 9]
    hi = tbl.astype(np.float32)
    lo = (tbl - hi.astype(np.float64)).astype(np.float32)
    return hi, lo


def _df_trig_local(d_deg: jnp.ndarray, origin_deg: float) -> Tuple[DF, DF]:
    """(sin, cos) of (origin + d) with origin folded in as df constants
    and the small-angle part by df Taylor series."""
    rad = df_mul(df_from_f32(d_deg), df_const(np.pi / 180.0))
    s_d, c_d = df_poly_sin(rad), df_poly_cos(rad)
    o = np.radians(np.float64(origin_deg))
    s0, c0 = df_const(np.sin(o)), df_const(np.cos(o))
    sin = df_add(df_mul(s0, c_d), df_mul(c0, s_d))
    cos = df_sub(df_mul(c0, c_d), df_mul(s0, s_d))
    return sin, cos


def project_lattice_jax(xy_deg: jnp.ndarray, res: int,
                        origin_deg: Optional[np.ndarray] = None,
                        precision: str = "auto"):
    """(lon, lat) degrees -> hex lattice position at ``res``.

    xy_deg [..., 2] f32 — origin-local when ``origin_deg`` (f64 host
    (lon0, lat0)) is given, else absolute.  Returns
    (face [...] i32, a [...] i32, b [...] i32, margin [...] f32,
    facegap [...] f32): axial lattice coords on the nearest icosahedron
    face, distance from the point to its hex cell's Voronoi boundary in
    lattice units, and the nearest-face dot-product gap (both are the
    device-side uncertainty signals; compare margin against
    err_lattice_bound(res, precision))."""
    p = pick_precision(precision)
    if p == "f64":
        return _project_f64(xy_deg, res, origin_deg)
    return _project_df(xy_deg, res, origin_deg)


def _project_f64(xy_deg: jnp.ndarray, res: int,
                 origin_deg: Optional[np.ndarray]):
    """Native-f64 projection (CPU tests / reference path)."""
    x = xy_deg[..., 0].astype(jnp.float64)
    y = xy_deg[..., 1].astype(jnp.float64)
    if origin_deg is not None:
        x = x + np.float64(origin_deg[0])
        y = y + np.float64(origin_deg[1])
    lat = jnp.radians(y)
    lng = jnp.radians(x)
    cl = jnp.cos(lat)
    xyz = jnp.stack([cl * jnp.cos(lng), cl * jnp.sin(lng), jnp.sin(lat)],
                    axis=-1)
    dots = xyz @ jnp.asarray(face_center_xyz().T)         # [..., 20]
    face = jnp.argmax(dots, axis=-1).astype(jnp.int32)
    m1 = jnp.max(dots, axis=-1)
    masked = jnp.where(jax.nn.one_hot(face, 20, dtype=bool),
                       -jnp.inf, dots)
    facegap = (m1 - jnp.max(masked, axis=-1)).astype(jnp.float32)

    e1, e2 = scaled_bases(res)
    onehot = jax.nn.one_hot(face, 20, dtype=jnp.float64)
    fc = onehot @ jnp.asarray(face_center_xyz())
    b1 = onehot @ jnp.asarray(e1)
    b2 = onehot @ jnp.asarray(e2)
    u = jnp.sum(xyz * fc, axis=-1)
    px = jnp.sum(xyz * b1, axis=-1) / u
    py = jnp.sum(xyz * b2, axis=-1) / u

    rf = py / np.float64(M_SIN60)
    qf = px - 0.5 * rf
    sf = -qf - rf
    rq, rr, rs = jnp.round(qf), jnp.round(rf), jnp.round(sf)
    dq = jnp.abs(rq - qf)
    dr = jnp.abs(rr - rf)
    ds = jnp.abs(rs - sf)
    fix_q = (dq > dr) & (dq > ds)
    fix_r = (~fix_q) & (dr > ds)
    rq = jnp.where(fix_q, -rr - rs, rq)
    rr = jnp.where(fix_r, -rq - rs, rr)
    fq = qf - rq
    fr = rf - rr
    ai = (rq + rr).astype(jnp.int32)
    bi = rr.astype(jnp.int32)
    vx = fq + 0.5 * fr
    vy = np.float64(M_SIN60) * fr
    h = 0.5 * vx
    sv = np.float64(M_SIN60) * vy
    proj = jnp.maximum(jnp.abs(vx),
                       jnp.maximum(jnp.abs(h + sv), jnp.abs(h - sv)))
    margin = jnp.maximum(0.5 - proj, 0.0).astype(jnp.float32)
    return face, ai, bi, margin, facegap


def _project_df(xy_deg: jnp.ndarray, res: int,
                origin_deg: Optional[np.ndarray]):
    """Double-single f32 projection (the TPU path)."""
    x = xy_deg[..., 0].astype(jnp.float32)
    y = xy_deg[..., 1].astype(jnp.float32)
    if origin_deg is not None:
        sin_lat, cos_lat = _df_trig_local(y, float(origin_deg[1]))
        sin_lng, cos_lng = _df_trig_local(x, float(origin_deg[0]))
    else:
        lat = jnp.radians(y)
        lng = jnp.radians(x)
        sin_lat = df_from_f32(jnp.sin(lat))
        cos_lat = df_from_f32(jnp.cos(lat))
        sin_lng = df_from_f32(jnp.sin(lng))
        cos_lng = df_from_f32(jnp.cos(lng))
    X = df_mul(cos_lat, cos_lng)
    Y = df_mul(cos_lat, sin_lng)
    Z = sin_lat

    c = _consts()
    xyz_hi = jnp.stack([X.hi, Y.hi, Z.hi], axis=-1)
    # full-f32 matmul: TPU's default matmul precision is bf16 passes,
    # which would smear face selection by ~4e-3 (observed as constant
    # 13-cell lattice offsets before HIGHEST was forced)
    dots = jnp.matmul(xyz_hi, c["face_xyz"].T,
                      precision=jax.lax.Precision.HIGHEST)  # [..., 20]
    face = jnp.argmax(dots, axis=-1).astype(jnp.int32)
    m1 = jnp.max(dots, axis=-1)
    masked = jnp.where(jax.nn.one_hot(face, 20, dtype=bool),
                       -jnp.inf, dots)
    m2 = jnp.max(masked, axis=-1)
    facegap = m1 - m2

    # per-face basis rows selected by exact masked sum (NOT a matmul:
    # one-hot x table must be bit-exact, MXU bf16 would truncate)
    onehot = jax.nn.one_hot(face, 20, dtype=jnp.float32)
    hi_t, lo_t = _basis_table(res)
    bhi = jnp.sum(onehot[..., None] * jnp.asarray(hi_t), axis=-2)
    blo = jnp.sum(onehot[..., None] * jnp.asarray(lo_t), axis=-2)

    def dot_basis(k):
        acc = df_mul(X, DF(bhi[..., k], blo[..., k]))
        acc = df_add(acc, df_mul(Y, DF(bhi[..., k + 1], blo[..., k + 1])))
        return df_add(acc, df_mul(Z, DF(bhi[..., k + 2], blo[..., k + 2])))

    u = dot_basis(0)
    px = df_div(dot_basis(3), u)
    py = df_div(dot_basis(6), u)

    # cube rounding in the 60°-basis axial frame (q, r) = (a - b, b)
    rf = df_mul(py, df_const(1.0 / M_SIN60))
    qf = df_sub(px, df_mul_f32(rf, np.float32(0.5)))
    sf = df_sub(qf.neg(), rf)
    rq, fq = df_round(qf)
    rr, fr = df_round(rf)
    rs, fs = df_round(sf)
    dq, dr, ds = jnp.abs(fq), jnp.abs(fr), jnp.abs(fs)
    fix_q = (dq > dr) & (dq > ds)
    fix_r = (~fix_q) & (dr > ds)
    rq2 = jnp.where(fix_q, -rr - rs, rq)
    rr2 = jnp.where(fix_r, -rq2 - rs, rr)
    # residuals relative to the FIXED lattice point (integer shifts of
    # f32 integers are exact)
    fq = fq + (rq - rq2)
    fr = fr + (rr - rr2)
    ai = (rq2 + rr2).astype(jnp.int32)
    bi = rr2.astype(jnp.int32)

    # distance to the hex Voronoi boundary: planar residual projected on
    # the three neighbor axes (0°, 60°, 120°); boundary at 0.5
    vx = fq + np.float32(0.5) * fr
    vy = np.float32(M_SIN60) * fr
    h = np.float32(0.5) * vx
    sv = np.float32(M_SIN60) * vy
    proj = jnp.maximum(jnp.abs(vx),
                       jnp.maximum(jnp.abs(h + sv), jnp.abs(h - sv)))
    margin = jnp.maximum(np.float32(0.5) - proj, np.float32(0.0))
    return face, ai, bi, margin, facegap


def cell_from_lattice_jax(face, ai, bi, res: int):
    """(face, axial a, axial b) at ``res`` -> canonical int64 cell ids
    (aperture-7 aggregation + base-cell lookup + digit rotation)."""
    c = _consts()
    digits = [None] * (res + 1)
    for rv in range(res, 0, -1):
        if _down_rot(rv):
            ua = _round_div7(2 * ai + bi)
            ub = _round_div7(3 * bi - ai)
            ca = 3 * ua - ub
            cb = ua + 2 * ub
        else:
            ua = _round_div7(3 * ai - bi)
            ub = _round_div7(ai + 2 * bi)
            ca = 2 * ua + ub
            cb = -ua + 3 * ub
        da = ai - ca
        db = bi - cb
        digits[rv] = c["digit_of_diff"][(da + 1) * 3 + (db + 1)]
        ai, bi = ua, ub

    # res-0 normalized ijk and base-cell entry
    mn = jnp.minimum(jnp.minimum(ai, bi), 0)
    i0 = ai - mn
    j0 = bi - mn
    k0 = -mn
    entry = ((face * 3 + i0) * 3 + j0) * 3 + k0
    base = c["fijk_base"][entry]
    r0 = c["fijk_rot"][entry]

    # rotate digits to canonical orientation
    lead = jnp.zeros_like(base)
    for rv in range(1, res + 1):
        digits[rv] = c["rot_digit"][r0 * 7 + digits[rv]]
        lead = jnp.where((lead == 0) & (digits[rv] != 0), digits[rv],
                         lead)
    # pentagon seam re-expression
    is_pent = c["is_pent"][base] == 1
    seam_hit = is_pent & (lead == c["pent_seam"][base]) & (lead != 0)
    extra = jnp.where(seam_hit, c["fijk_extra"][entry], 0)
    # internal -> published pentagon labels: after the extra rotation,
    # subtrees with leading digit 1 or 5 rotate ccw once (index.py
    # _pent_to_external carries the derivation)
    lead_f = c["rot_digit"][extra * 7 + lead]
    relabel = jnp.where(is_pent & ((lead_f == 1) | (lead_f == 5)), 1, 0)
    h = (jnp.int64(MODE_CELL) << _MODE_SHIFT) | \
        (jnp.int64(res) << _RES_SHIFT) | \
        (base.astype(jnp.int64) << _BASE_SHIFT)
    fill = np.int64(0)
    for rv in range(res + 1, 16):
        fill |= np.int64(7) << _digit_shift(rv)
    h = h | jnp.int64(fill)
    for rv in range(1, res + 1):
        d = c["rot_digit"][extra * 7 + digits[rv]]
        d = c["rot_digit"][relabel * 7 + d]
        h = h | (d.astype(jnp.int64) << _digit_shift(rv))
    return h


def latlng_to_cell_jax(lat, lng, res: int):
    """lat, lng (radians) -> int64 cell ids; shapes broadcast."""
    return latlng_to_cell_jax_margin(lat, lng, res)[0]


def latlng_to_cell_jax_margin(lat, lng, res: int):
    """(cells, margin): margin is the approximate angular distance
    (radians) from each point to its hex cell's boundary — the
    device-side uncertainty signal.  Absolute-coordinate path; the PIP
    join uses project_lattice_jax with an origin for the precise one."""
    xy = jnp.stack([jnp.degrees(lng.astype(jnp.float32)),
                    jnp.degrees(lat.astype(jnp.float32))], axis=-1)
    face, ai, bi, margin, facegap = project_lattice_jax(xy, res)
    cells = cell_from_lattice_jax(face, ai, bi, res)
    # lattice units -> radians (gnomonic scale; distortion only enlarges
    # planar distances, and face-ambiguous points get margin 0)
    margin = margin * np.float32(RES0_U_GNOMONIC / M_SQRT7 ** res)
    margin = jnp.where(facegap < FACEGAP_EPS, np.float32(0.0), margin)
    return cells, margin
