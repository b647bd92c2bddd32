"""Trace-driven workload replayer (ISSUE 19, testing/replay.py).

The generator is a pure function of its config, so every property is
testable without a cluster: determinism (same seed ⇒ bit-identical
trace), the Zipf hot-set shape, the size-mixture bands, the diurnal
arrival envelope, and the op-mix fractions.  These are the acceptance
teeth behind "a chaos run is exactly reproducible": the
trace_signature this suite pins down.
"""

import math

from garage_tpu.testing.replay import (
    SIZE_PRESETS,
    ReplayConfig,
    body_for,
    generate_ops,
    trace_signature,
    zipf_cdf,
)

# a longer, denser config for the statistical shape assertions — still
# pure generation, runs in milliseconds
SHAPE_CFG = ReplayConfig(seed=4242, n_keys=128, zipf_theta=1.1,
                         base_ops_per_s=50.0, duration_s=24.0,
                         diurnal_amplitude=0.6, diurnal_period_s=8.0)


# --- determinism -------------------------------------------------------


def test_same_seed_same_trace():
    cfg = ReplayConfig(seed=7)
    a, b = generate_ops(cfg), generate_ops(cfg)
    assert a == b
    assert trace_signature(a) == trace_signature(b)


def test_different_seed_different_trace():
    assert (trace_signature(generate_ops(ReplayConfig(seed=1)))
            != trace_signature(generate_ops(ReplayConfig(seed=2))))


def test_signature_sensitive_to_every_field():
    ops = generate_ops(ReplayConfig(seed=7))
    sig = trace_signature(ops)
    kind, key, size, at = ops[len(ops) // 2]
    mutated = list(ops)
    mutated[len(ops) // 2] = (kind, key, size + 1, at)
    assert trace_signature(mutated) != sig


def test_body_deterministic_and_version_unique():
    cfg = ReplayConfig(seed=9)
    assert body_for(cfg, 3, 1, 4096) == body_for(cfg, 3, 1, 4096)
    assert body_for(cfg, 3, 1, 4096) != body_for(cfg, 3, 2, 4096)
    assert body_for(cfg, 3, 1, 4096) != body_for(cfg, 4, 1, 4096)
    assert len(body_for(cfg, 0, 1, 777)) == 777


# --- Zipf hot-set shape -----------------------------------------------


def test_zipf_cdf_is_monotone_and_normalized():
    cdf = zipf_cdf(128, 1.1)
    assert len(cdf) == 128
    assert all(b > a for a, b in zip(cdf, cdf[1:]))
    assert math.isclose(cdf[-1], 1.0)


def test_zipf_key_popularity():
    """θ=1.1 over 128 keys: rank 0 takes ~19% of picks, the top 10
    ~50% — the analytic shares, with generous sampling slack."""
    ops = generate_ops(SHAPE_CFG)
    keys = [k for _kind, k, _s, _t in ops]
    assert len(keys) > 500
    n = len(keys)
    top1 = keys.count(0) / n
    top10 = sum(1 for k in keys if k < 10) / n
    assert top1 > 0.15, top1
    assert top10 > 0.45, top10
    # ...but it is a distribution, not a constant: the tail is touched
    assert len(set(keys)) > 32


# --- size mixture ------------------------------------------------------


def test_sizes_stay_inside_preset_bands():
    ops = generate_ops(SHAPE_CFG)
    bands = SIZE_PRESETS[SHAPE_CFG.size_preset]
    sizes = [s for kind, _k, s, _t in ops if kind == "put"]
    assert len(sizes) > 200
    counts = [0] * len(bands)
    for s in sizes:
        for bi, (_p, lo, hi) in enumerate(bands):
            if lo <= s < hi:
                counts[bi] += 1
                break
        else:
            raise AssertionError(f"size {s} outside every band")
    # the 80% band dominates, and even the 2% band is represented
    assert 0.68 <= counts[0] / len(sizes) <= 0.9, counts
    assert counts[-1] >= 1, counts


def test_multipart_preset_reaches_multipart_sizes():
    cfg = ReplayConfig(seed=11, size_preset="multipart",
                       base_ops_per_s=30.0, duration_s=20.0)
    sizes = [s for kind, _k, s, _t in generate_ops(cfg) if kind == "put"]
    assert max(sizes) >= 8 << 20          # the 8–16 MiB band was hit
    assert min(sizes) >= 256 << 10        # nothing below the preset


# --- diurnal arrival envelope -----------------------------------------


def test_diurnal_peak_vs_trough_density():
    """rate(t) = base·(1 + a·sin(2πt/P)): with a=0.6 the quarter-period
    window centered on the peak carries ~3.3× the ops of the trough
    window — assert a conservative ≥ 2×."""
    ops = generate_ops(SHAPE_CFG)
    period = SHAPE_CFG.diurnal_period_s
    peak = trough = 0
    for _kind, _k, _s, at in ops:
        phase = (at % period) / period
        if 0.125 <= phase < 0.375:        # centered on sin's max (0.25)
            peak += 1
        elif 0.625 <= phase < 0.875:      # centered on sin's min (0.75)
            trough += 1
    assert trough > 0
    assert peak / trough >= 2.0, (peak, trough)


def test_timestamps_sorted_and_bounded():
    ops = generate_ops(SHAPE_CFG)
    ats = [at for _kind, _k, _s, at in ops]
    assert ats == sorted(ats)
    assert 0.0 < ats[0] and ats[-1] < SHAPE_CFG.duration_s


# --- op mix ------------------------------------------------------------


def test_op_mix_fractions():
    ops = generate_ops(SHAPE_CFG)
    n = len(ops)
    gets = sum(1 for kind, *_ in ops if kind == "get") / n
    dels = sum(1 for kind, *_ in ops if kind == "delete") / n
    assert abs(gets - SHAPE_CFG.read_fraction) < 0.05, gets
    assert 0.005 <= dels <= 0.08, dels
