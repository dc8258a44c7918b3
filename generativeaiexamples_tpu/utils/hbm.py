"""Peak HBM bandwidth by TPU generation — the denominator of every
roofline number the repo reports (bench.py ``hbm_utilization``,
tools/profile_decode.py ``achieved_bw_fraction``). Single-sourced so a
new generation (or a corrected spec number) lands in every artifact at
once."""

from __future__ import annotations

# Peak HBM bandwidth (bytes/s), matched as a substring of the lower-cased
# ``device_kind`` JAX reports. Source: Google Cloud TPU documentation,
# the system-architecture page of each generation ("TPU v5e": 819 GB/s).
PEAK_HBM_BW = {
    "v4": 1.2e12,
    "v5 lite": 819e9, "v5e": 819e9,
    "v5p": 2.76e12,
    "v6 lite": 1.64e12, "v6e": 1.64e12,
}


def peak_bw(device) -> float:
    """Peak HBM bytes/s for a jax device. A kind the table does not list
    raises: a roofline share against another chip's peak is not a
    number."""
    kind = getattr(device, "device_kind", "").lower()
    for key, bw in PEAK_HBM_BW.items():
        if key in kind:
            return bw
    raise ValueError(
        f"no peak HBM bandwidth for device kind {kind!r}; add it to "
        f"PEAK_HBM_BW (utils/hbm.py) with its source")
