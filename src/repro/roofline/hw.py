"""Named hardware profiles for the analytic performance models.

Seed versions of this module hardcoded one TPU-v5e constant table; the
cost model (``repro.cost``) and the roofline analysis now resolve their
peak-FLOPs / bandwidth / memory numbers through a :class:`HardwareProfile`
registry instead, so the same analytic machinery prices kernels and graph
schedules for any target the registry names.

Profiles:

* ``tpu_v5e`` (default) — one v5e chip: 197 TFLOP/s bf16, 819 GB/s HBM,
  ~128 MiB VMEM, 4 usable ICI links at 50 GB/s.
* ``cpu_interpret`` — the Pallas interpret path this repo's CI runs on.
  The absolute numbers are a deliberately small proxy (interpret mode is
  not a hardware target); only *relative* ordering of candidates is
  meaningful, which is all the autotune pruner needs off-TPU.

Selection: :func:`get_profile` resolves an explicit name, else on a TPU
backend the attached chip's ``device_kind`` (:data:`TPU_KINDS`; an unknown
kind raises), else the ``REPRO_HW_PROFILE`` environment variable, else
``tpu_v5e``.  New targets
register with :func:`register_profile`; ``tools/check_docs.py`` requires
every registered profile name to be documented in ``docs/cost_model.md``.

The module-level constants (``PEAK_FLOPS_BF16`` ...) are the ``tpu_v5e``
numbers, kept for existing call sites that predate the registry.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional

_ENV_VAR = "REPRO_HW_PROFILE"
DEFAULT_PROFILE = "tpu_v5e"


@dataclasses.dataclass(frozen=True)
class HardwareProfile:
    """Analytic description of one execution target.

    All rates are per chip/device; the roofline's collective term combines
    ``ici_bw_per_link`` with ``links_per_chip``.
    """

    name: str
    peak_flops: float            # FLOP/s (the dominant matmul dtype)
    hbm_bw: float                # bytes/s main-memory bandwidth
    vmem_bytes: int              # on-chip scratch ceiling (tile residency)
    hbm_bytes: int               # main-memory capacity
    ici_bw_per_link: float = 0.0  # bytes/s per interconnect link
    links_per_chip: int = 0
    description: str = ""

    @property
    def ridge_intensity(self) -> float:
        """FLOP/byte where compute and memory time balance."""
        return self.peak_flops / self.hbm_bw


_PROFILES: Dict[str, HardwareProfile] = {}


def register_profile(profile: HardwareProfile) -> HardwareProfile:
    if profile.name in _PROFILES:
        raise ValueError(f"hardware profile {profile.name!r} already "
                         "registered")
    _PROFILES[profile.name] = profile
    return profile


#: ``device_kind`` as JAX reports it -> profile of that chip
TPU_KINDS: Dict[str, str] = {"TPU v5 lite": "tpu_v5e"}


def get_profile(name: Optional[str] = None) -> HardwareProfile:
    """Resolve a profile: explicit ``name``; else, on a TPU backend, the
    profile of the attached chip's ``device_kind`` (an unknown kind is an
    error, never a default); else ``$REPRO_HW_PROFILE`` > ``tpu_v5e``."""
    resolved = name or _tpu_profile_name() or os.environ.get(_ENV_VAR) \
        or DEFAULT_PROFILE
    try:
        return _PROFILES[resolved]
    except KeyError:
        raise KeyError(f"no hardware profile {resolved!r}; "
                       f"known: {sorted(_PROFILES)}") from None


def _tpu_profile_name() -> Optional[str]:
    import jax

    if jax.default_backend() != "tpu":
        return None
    kind = jax.devices()[0].device_kind
    if kind not in TPU_KINDS:
        raise KeyError(f"no hardware profile for TPU device kind {kind!r}; "
                       f"known kinds: {sorted(TPU_KINDS)}")
    return TPU_KINDS[kind]


def all_profiles() -> Dict[str, HardwareProfile]:
    return dict(_PROFILES)


register_profile(HardwareProfile(
    name="tpu_v5e",
    peak_flops=197e12,
    hbm_bw=819e9,
    vmem_bytes=128 * 1024 * 1024,
    hbm_bytes=16 * 1024**3,
    ici_bw_per_link=50e9,
    links_per_chip=4,
    description="one TPU v5e chip (bf16 MXU peak, 2D-torus ICI)",
))

register_profile(HardwareProfile(
    name="cpu_interpret",
    peak_flops=5e9,
    hbm_bw=20e9,
    vmem_bytes=16 * 1024 * 1024,
    hbm_bytes=8 * 1024**3,
    ici_bw_per_link=0.0,
    links_per_chip=0,
    description="Pallas interpret mode on CPU — ordering-only proxy; "
                "absolute estimates are not hardware predictions",
))


# -- legacy constant aliases (the tpu_v5e numbers) --------------------------
_V5E = _PROFILES["tpu_v5e"]

PEAK_FLOPS_BF16 = _V5E.peak_flops     # FLOP/s per chip
HBM_BW = _V5E.hbm_bw                  # bytes/s per chip
ICI_BW_PER_LINK = _V5E.ici_bw_per_link  # bytes/s per link

SINGLE_POD_CHIPS = 256
MULTI_POD_CHIPS = 512
VMEM_BYTES = _V5E.vmem_bytes
HBM_BYTES = _V5E.hbm_bytes
