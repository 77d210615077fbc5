"""Score-network configs for the paper's own experiments (VE/VP models),
plus the serving tier's tolerance-class presets (DESIGN.md §14).

``cifar_dit`` mirrors the paper's CIFAR-10 32×32 setting at a trainable
scale; ``highres_dit`` stands in for the LSUN/FFHQ 256×256 setting (used
by the table-2 benchmark at reduced resolution on CPU, full resolution
under the dry-run). ``toy_mlp`` is the exactly-solvable 2-D setting used
for solver validation. ``traj_unet`` is the trajectory workload's
temporal score network (DESIGN.md §10) at a locomotion-style shape.
"""

import dataclasses
from typing import Optional

from repro.models.dit import DiTConfig
from repro.models.score_unet import MLPScoreConfig, UNetConfig
from repro.models.temporal_unet import TemporalUNetConfig


@dataclasses.dataclass(frozen=True)
class ToleranceClass:
    """A per-request quality tier (DESIGN.md §14): the adaptive solver's
    error tolerance as a *runtime* admission knob, not a config rebuild.

    The paper's Table 1 sweeps ε from 0.01 (best FID) to 0.5 (2–10×
    fewer NFE); a tier names a point on that frontier. ``eps_abs=None``
    defers to ``sde.abs_tolerance`` (the image-calibrated default, same
    resolution rule as ``AdaptiveConfig.eps_abs``); ``h_init=None``
    defers to the serving config's ``h_init``. ``deadline_ms`` is the
    tier's default latency budget (None = no deadline) and ``priority``
    its default admission band (lower = more urgent) — both are
    per-request overridable.
    """

    name: str
    eps_rel: float
    eps_abs: Optional[float] = None
    h_init: Optional[float] = None
    deadline_ms: Optional[float] = None
    priority: int = 0


#: paper-Table-1 frontier presets: draft trades W2 for the 2–10× NFE
#: cut (ε=0.5, the paper's cheapest setting), standard is the repo's
#: serving default (ε=0.05), high_fidelity the paper's best-FID ε=0.01.
DRAFT = ToleranceClass("draft", eps_rel=0.5, priority=1)
STANDARD = ToleranceClass("standard", eps_rel=0.05, priority=1)
HIGH_FIDELITY = ToleranceClass("high_fidelity", eps_rel=0.01, priority=0)

TOLERANCE_CLASSES = {c.name: c for c in (DRAFT, STANDARD, HIGH_FIDELITY)}


def resolve_tier(tier) -> ToleranceClass:
    """Preset name or ToleranceClass instance → ToleranceClass."""
    if isinstance(tier, ToleranceClass):
        return tier
    if tier in TOLERANCE_CLASSES:
        return TOLERANCE_CLASSES[tier]
    raise KeyError(
        f"unknown tolerance class {tier!r}; presets: "
        f"{sorted(TOLERANCE_CLASSES)} (or pass a ToleranceClass)"
    )

# Paper Table 1 analog (CIFAR-scale, 32×32×3)
CIFAR_DIT = DiTConfig(
    image_size=32, channels=3, patch=4, d_model=256, num_layers=6,
    num_heads=8, d_ff=1024,
)
CIFAR_UNET = UNetConfig(image_size=32, channels=3, base=32, mults=(1, 2, 2))

# Paper Table 2 analog (high-res, 256×256×3) — dry-run / lowering scale
HIGHRES_DIT = DiTConfig(
    image_size=256, channels=3, patch=16, d_model=768, num_layers=12,
    num_heads=12, d_ff=3072,
)

# ~100M-param DiT for the end-to-end example's full preset
DIT_100M = DiTConfig(
    image_size=32, channels=3, patch=2, d_model=768, num_layers=12,
    num_heads=12, d_ff=3072,
)

#: the serving launcher's default: 8×8, width 32, 2 layers — small
#: enough that CPU tests and demos of the serve loop stay fast
SMALL_DIT = DiTConfig(
    image_size=8, channels=3, patch=4, d_model=32, num_layers=2,
    num_heads=2, d_ff=64,
)

#: DiTs ``launch/serve --diffusion --net NAME`` can serve
DIT_NETS = {"small": SMALL_DIT, "cifar": CIFAR_DIT, "highres": HIGHRES_DIT}

TOY_MLP = MLPScoreConfig(dim=2, hidden=128, depth=3)

# Trajectory-diffusion planning workload (DESIGN.md §10): horizon-32
# plans over a locomotion-style transition (obs 17 + act 6 = 23), with
# returns-to-go CFG bins (decision-diffuser setting)
TRAJ_UNET = TemporalUNetConfig(
    horizon=32, transition_dim=23, base=32, mults=(1, 2, 4), t_dim=64,
    returns_bins=10,
)
