"""LEMUR configuration (paper App. A defaults).

Retriever API v1 splits the config into a build-time core (the reduction:
d', training sizes, OLS) plus one **per-backend namespace** per registered
first-stage backend (``cfg.ivf``, ``cfg.muvera``, ``cfg.dessert``,
``cfg.token_pruning``, ``cfg.bruteforce`` — the ``config_cls`` types
registered alongside each backend in :mod:`repro.anns.registry`):

    cfg = LemurConfig(anns="ivf", ivf=IVFBackendConfig(nprobe=64, sq8=True))
    cfg.backend_config()          # -> the active backend's namespace
    cfg.override({"ivf.nprobe": 16})   # dotted CLI overrides reach inside

The v0 flat knobs (``ivf_nprobe``, ``sq8``, ``dessert_tables``, …) keep
working as **deprecated aliases**: constructor kwargs and ``replace()`` keys
are folded into the matching namespace, and attribute reads forward to it —
both emit a ``DeprecationWarning`` naming the replacement.
"""
from __future__ import annotations

import dataclasses
import warnings

from repro.anns.params import (
    BruteforceBackendConfig,
    DessertBackendConfig,
    IVFBackendConfig,
    MuveraBackendConfig,
    ResidualConfig,
    TokenPruningBackendConfig,
)
from repro.common.config import ConfigBase

# v0 flat knob -> (namespace field, field inside it)
_LEGACY_KNOBS = {
    "ivf_nlist": ("ivf", "nlist"),
    "ivf_nprobe": ("ivf", "nprobe"),
    "sq8": ("ivf", "sq8"),
    "dessert_tables": ("dessert", "tables"),
    "dessert_bits": ("dessert", "bits"),
    "muvera_r_reps": ("muvera", "r_reps"),
    "muvera_k_sim": ("muvera", "k_sim"),
    "muvera_final_dim": ("muvera", "final_dim"),
    "tp_nlist": ("token_pruning", "nlist"),
    "tp_nprobe": ("token_pruning", "nprobe"),
}


@dataclasses.dataclass(frozen=True)
class LemurConfig(ConfigBase):
    d: int = 128                 # token embedding dim (ColBERTv2: 128)
    d_prime: int = 2048          # latent dim d' (ablated 1024/2048/4096, §6.2)
    m_pretrain: int = 8192       # m': sampled docs as pretraining targets
    n_train: int = 100_000       # n: token embeddings in the MLP training set
    n_ols: int = 16_384          # n': tokens for the OLS solutions
    lr: float = 3e-3
    epochs: int = 100
    batch_size: int = 512
    grad_clip: float = 0.5
    ridge: float = 1e-4          # OLS regularizer (numerical; paper uses plain OLS)
    query_strategy: str = "corpus-query"  # corpus-query | corpus | query (§4.2)
    k: int = 100                 # final top-k
    k_prime: int = 1024          # candidates to rerank
    anns: str = "ivf"            # first-stage backend name (anns/registry.py):
                                 # bruteforce|ivf|muvera|dessert|token_pruning
                                 # ("exact" = legacy alias for bruteforce)
    # per-backend namespaces (used only when `anns` selects that backend)
    bruteforce: BruteforceBackendConfig = BruteforceBackendConfig()
    ivf: IVFBackendConfig = IVFBackendConfig()
    muvera: MuveraBackendConfig = MuveraBackendConfig()
    dessert: DessertBackendConfig = DessertBackendConfig()
    token_pruning: TokenPruningBackendConfig = TokenPruningBackendConfig()
    # compressed token-corpus tier (codec + constant-space pooling): OFF by
    # default — fp32 paged store; enabling is a BUILD-time decision (the
    # corpus must be encoded), use_residual on SearchParams only selects
    # which store a compiled query fn reads
    residual: ResidualConfig = ResidualConfig()
    rerank_block: int = 1024     # docs per MaxSim rerank tile
    use_fused_gather: bool = True  # candidate-gather rerank through the
                                   # gather-at-source kernel path (kernels.
                                   # gather_scan); False = legacy HBM gather.
                                   # The IVF probe-scan twin lives in
                                   # cfg.ivf.use_fused_gather.
    use_one_launch: bool = False   # fuse the pre-rerank first stage (ψ-pool +
                                   # scan + top-k') into ONE kernel launch
                                   # (kernels.query_fused) for the exact scan
                                   # and the sharded serve step.  The IVF twin
                                   # lives in cfg.ivf.use_one_launch.
    score_dtype: str = "float32"
    build_per_shard: bool = False  # a corpus larger than one chip: build()
                                   # without a mesh stops after ψ and the
                                   # OLS solver (sharded.TrainedLemur), and
                                   # its shard(mesh) builds each shard's
                                   # index on that shard's chip, as
                                   # build(mesh=) does

    def __post_init__(self):
        from repro.anns import registry  # late: keeps config import-light

        known = set(registry.list_backends()) | {"exact"}
        if self.anns not in known:
            raise ValueError(
                f"anns={self.anns!r} is not a registered backend; "
                f"known: {sorted(known)}"
            )

    def backend_config(self, name: str | None = None):
        """The config namespace for ``name`` (default: the active backend)."""
        from repro.anns import registry

        return getattr(self, registry.canonical(name or self.anns))

    @classmethod
    def from_dict(cls, d: dict) -> "LemurConfig":
        # v0 dicts/JSON carry the flat knobs as top-level keys; fold them
        # through the same deprecation path as constructor kwargs instead of
        # silently dropping them (ConfigBase.from_dict skips unknown keys)
        d = dict(d)
        legacy = {k: d.pop(k) for k in list(d) if k in _LEGACY_KNOBS}
        cfg = super().from_dict(d)
        return cfg.replace(**legacy) if legacy else cfg

    def __getattr__(self, name: str):
        # read-compat for the v0 flat knobs: cfg.ivf_nprobe -> cfg.ivf.nprobe
        if name in _LEGACY_KNOBS:
            sub, field = _LEGACY_KNOBS[name]
            warnings.warn(
                f"LemurConfig.{name} is deprecated; read cfg.{sub}.{field}",
                DeprecationWarning, stacklevel=2)
            return getattr(getattr(self, sub), field)
        raise AttributeError(
            f"{type(self).__name__!r} object has no attribute {name!r}")


def _fold_legacy_kwargs(kwargs: dict) -> dict:
    """Fold v0 flat knobs into their namespace (deprecation path)."""
    legacy = {k: kwargs.pop(k) for k in list(kwargs) if k in _LEGACY_KNOBS}
    if not legacy:
        return kwargs
    warnings.warn(
        "flat LemurConfig knobs are deprecated: "
        + ", ".join(f"{k} -> {_LEGACY_KNOBS[k][0]}.{_LEGACY_KNOBS[k][1]}"
                    for k in legacy),
        DeprecationWarning, stacklevel=3)
    for k, v in legacy.items():
        sub, field = _LEGACY_KNOBS[k]
        base = kwargs.get(sub, LemurConfig.__dataclass_fields__[sub].default)
        kwargs[sub] = base.replace(**{field: v})
    return kwargs


_dataclass_init = LemurConfig.__init__


def _compat_init(self, *args, **kwargs):
    _dataclass_init(self, *args, **_fold_legacy_kwargs(kwargs))


LemurConfig.__init__ = _compat_init
