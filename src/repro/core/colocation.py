"""VIIRS→CrIS satellite observation co-location — the paper's application.

Reimplements the paper's proof-of-concept workload (Fig. 7/8; Wang et al.
2016, Remote Sensing 8(1):76) fully in JAX so the NavP machinery has a real
science-data job to migrate:

  stage 1  read VIIRS + CrIS granules      (synthetic orbital geometry here)
  stage 2  compute CrIS LOS vectors in ECEF
           compute VIIRS POS vectors in ECEF
  stage 3  match VIIRS pixels to CrIS FOVs (angular nearest-neighbor)
  stage 4  write product

The match (stage 3) is the compute hot-spot: an N×M angular argmax with
N ≈ millions of VIIRS pixels and M ≈ thousands of CrIS fields-of-view. A
Pallas TPU kernel (`repro.kernels.colocate`) blocks it through VMEM; this
module carries the pure-jnp oracle the kernel is validated against.

Geometry notes: WGS-84 geodetic→ECEF; CrIS FOV nominal diameter 0.963°; a
VIIRS pixel matches a CrIS FOV when the angle between (pixel_pos − sat_pos)
and the FOV line-of-sight is below the half-angle.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# WGS-84
_A = 6378137.0  # semi-major axis, m
_F = 1.0 / 298.257223563
_E2 = _F * (2 - _F)

CRIS_FOV_DIAMETER_DEG = 0.963


def geodetic_to_ecef(lat_deg: jax.Array, lon_deg: jax.Array, alt_m: jax.Array | float = 0.0):
    """WGS-84 geodetic coordinates → ECEF, shape [..., 3] (meters)."""
    lat = jnp.deg2rad(lat_deg)
    lon = jnp.deg2rad(lon_deg)
    sin_lat, cos_lat = jnp.sin(lat), jnp.cos(lat)
    n = _A / jnp.sqrt(1.0 - _E2 * sin_lat**2)
    x = (n + alt_m) * cos_lat * jnp.cos(lon)
    y = (n + alt_m) * cos_lat * jnp.sin(lon)
    z = (n * (1.0 - _E2) + alt_m) * sin_lat
    return jnp.stack([x, y, z], axis=-1)


def _unit(v: jax.Array, axis: int = -1) -> jax.Array:
    return v / jnp.linalg.norm(v, axis=axis, keepdims=True)


# ---------------------------------------------------------------------------
# synthetic granules (stage 1)
# ---------------------------------------------------------------------------


def make_synthetic_granules(
    seed: int = 0,
    *,
    n_scans: int = 16,
    cris_for_per_scan: int = 30,
    cris_fov_per_for: int = 9,
    viirs_pixels_per_scan: int = 3200,
    viirs_lines_per_scan: int = 16,
    orbit_alt_m: float = 824_000.0,  # Suomi-NPP
    swath_half_deg: float = 8.0,
) -> dict[str, Any]:
    """Generate co-registered synthetic VIIRS/CrIS granules along one track.

    Both instruments view the same ground swath from the same platform (SNPP
    carries both), so true matches exist by construction; jitter makes the
    nearest-neighbor problem non-trivial.
    """
    rng = np.random.default_rng(seed)
    # ground track: inclined great-circle-ish path
    t = np.linspace(0.0, 1.0, n_scans)
    track_lat = -20.0 + 40.0 * t
    track_lon = 120.0 + 10.0 * t

    def cross_track(n, jitter):
        off = np.linspace(-swath_half_deg, swath_half_deg, n)
        return off + rng.normal(0, jitter, size=off.shape)

    # CrIS: n_scans × (FOR × FOV) field centres
    cris_lat, cris_lon = [], []
    for i in range(n_scans):
        offs = cross_track(cris_for_per_scan * cris_fov_per_for, 0.02)
        cris_lat.append(np.full_like(offs, track_lat[i]) + rng.normal(0, 0.05, offs.shape))
        cris_lon.append(track_lon[i] + offs)
    cris_lat = np.concatenate(cris_lat)
    cris_lon = np.concatenate(cris_lon)

    # VIIRS: denser sampling of the same swath
    viirs_lat, viirs_lon = [], []
    for i in range(n_scans):
        for line in range(viirs_lines_per_scan):
            offs = np.linspace(-swath_half_deg, swath_half_deg, viirs_pixels_per_scan)
            lat_line = track_lat[i] + (line - viirs_lines_per_scan / 2) * 0.01
            viirs_lat.append(np.full_like(offs, lat_line) + rng.normal(0, 0.003, offs.shape))
            viirs_lon.append(track_lon[i] + offs + rng.normal(0, 0.003, offs.shape))
    viirs_lat = np.concatenate(viirs_lat)
    viirs_lon = np.concatenate(viirs_lon)

    # satellite position above the mid-track point (single-position model)
    sat_pos = np.asarray(
        geodetic_to_ecef(
            jnp.asarray(track_lat.mean()), jnp.asarray(track_lon.mean()), orbit_alt_m
        )
    )
    # synthetic radiances to aggregate in the product
    viirs_rad = rng.standard_normal(viirs_lat.shape).astype(np.float32) + 5.0
    return {
        "cris_lat": cris_lat.astype(np.float32),
        "cris_lon": cris_lon.astype(np.float32),
        "viirs_lat": viirs_lat.astype(np.float32),
        "viirs_lon": viirs_lon.astype(np.float32),
        "viirs_rad": viirs_rad,
        "sat_pos": sat_pos.astype(np.float64),
    }


# ---------------------------------------------------------------------------
# geometry (stage 2)
# ---------------------------------------------------------------------------


def cris_los_ecef(cris_lat, cris_lon, sat_pos) -> jax.Array:
    """Unit line-of-sight vectors sat → CrIS FOV ground intersection, [M, 3]."""
    fov_pos = geodetic_to_ecef(cris_lat, cris_lon, 0.0)
    return _unit(fov_pos - sat_pos[None, :])


def viirs_pos_ecef(viirs_lat, viirs_lon) -> jax.Array:
    """VIIRS pixel ground positions in ECEF, [N, 3]."""
    return geodetic_to_ecef(viirs_lat, viirs_lon, 0.0)


# ---------------------------------------------------------------------------
# match (stage 3) — pure-jnp oracle; the Pallas kernel mirrors this
# ---------------------------------------------------------------------------


def match_viirs_to_cris_ref(
    viirs_pos: jax.Array,  # [N, 3] ECEF
    cris_los: jax.Array,  # [M, 3] unit
    sat_pos: jax.Array,  # [3]
    *,
    half_angle_deg: float = CRIS_FOV_DIAMETER_DEG / 2,
    block_n: int = 65536,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """For each VIIRS pixel: (best CrIS index, best cosine, within-FOV mask).

    Scans VIIRS in blocks so the N×M score matrix is never materialised in
    full — the reference is itself HBM-feasible, the kernel adds VMEM tiling.
    """
    u = _unit(viirs_pos - sat_pos[None, :]).astype(jnp.float32)  # [N,3]
    los = cris_los.astype(jnp.float32)  # [M,3]
    cos_thr = jnp.cos(jnp.deg2rad(half_angle_deg)).astype(jnp.float32)
    n = u.shape[0]
    nb = -(-n // block_n)
    pad = nb * block_n - n
    u_p = jnp.pad(u, ((0, pad), (0, 0)))

    def body(carry, ub):
        scores = ub @ los.T  # [block, M]
        bi = jnp.argmax(scores, axis=1)
        bc = jnp.max(scores, axis=1)
        return carry, (bi.astype(jnp.int32), bc)

    _, (idx, cos) = jax.lax.scan(body, None, u_p.reshape(nb, block_n, 3))
    idx = idx.reshape(-1)[:n]
    cos = cos.reshape(-1)[:n]
    return idx, cos, cos >= cos_thr


def match_viirs_to_cris(viirs_pos, cris_los, sat_pos, **kw):
    """Kernel-accelerated match (Pallas; interpreted off-TPU)."""
    from repro.kernels.colocate.ops import colocate_match

    half = kw.get("half_angle_deg", CRIS_FOV_DIAMETER_DEG / 2)
    u = _unit(viirs_pos - sat_pos[None, :]).astype(jnp.float32)
    idx, cos = colocate_match(u, cris_los.astype(jnp.float32))
    thr = jnp.cos(jnp.deg2rad(half)).astype(jnp.float32)
    return idx, cos, cos >= thr


# ---------------------------------------------------------------------------
# product (stage 4)
# ---------------------------------------------------------------------------


def build_product(granules: dict, idx: jax.Array, within: jax.Array) -> dict[str, Any]:
    """Aggregate matched VIIRS radiances per CrIS FOV (mean + count)."""
    m = granules["cris_lat"].shape[0]
    rad = jnp.asarray(granules["viirs_rad"])
    w = within.astype(jnp.float32)
    counts = jax.ops.segment_sum(w, idx, num_segments=m)
    sums = jax.ops.segment_sum(rad * w, idx, num_segments=m)
    mean = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), jnp.nan)
    return {
        "cris_mean_rad": np.asarray(mean),
        "cris_match_count": np.asarray(counts, dtype=np.int32),
        "matched_frac": float(jnp.mean(w)),
    }
