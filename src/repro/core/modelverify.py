"""DEPRECATED shims: the model-level entry points moved to ``repro.verify``.

``verify_model_tp(arch, tp)`` / ``verify_decode_tp(arch, tp)`` remain as
thin wrappers over ``repro.verify.Session`` so existing call sites keep
working, but new code should use the Session API directly:

    from repro.verify import Session, Plan
    Session().verify(arch, Plan(tp=16))          # == verify_model_tp
    Session().verify(arch, Plan.decode(tp=16))   # == verify_decode_tp

The trace/stamp builders these entry points used live in
``repro.verify.pairs``; the spec-to-fact helpers in ``repro.verify.specs``.
"""
from __future__ import annotations

import warnings
from typing import Optional

from .verifier import Report, VerifyOptions

# names that already warned — each deprecated entry point emits exactly
# once per process (tests reset this set directly).  Removal timeline:
# docs/API.md.
_warned: set = set()


def _warn(old: str, new: str) -> None:
    if old in _warned:
        return
    _warned.add(old)
    warnings.warn(f"{old} is deprecated; use {new}",
                  DeprecationWarning, stacklevel=3)


def _session(options):
    from repro.verify import Session

    return Session(options=options)


def _tp1_report(arch: str, *, decode: bool, smoke: bool, batch: int,
                dim2: int, n_layers: Optional[int], options, mutate_dist):
    """Legacy tp=1 behavior: the Plan API rejects a degenerate plan, but the
    old one-shots traced the trivial pair and returned a Report — keep that
    for existing callers.  ``dim2`` is seq (forward) or max_len (decode)."""
    from repro.configs import get_config
    from repro.verify.pairs import round_layers, tp_decode_pair, tp_forward_pair

    from .verifier import verify_graphs

    options = options or VerifyOptions()
    cfg = round_layers(get_config(arch, smoke=smoke), n_layers)
    build = tp_decode_pair if decode else tp_forward_pair
    pair = build(arch, cfg, 1, batch, dim2, stamp=options.stamp)
    dist = pair.dist
    if mutate_dist is not None:
        dist = mutate_dist(dist)
        dist.stamp = None
    return verify_graphs(
        pair.base, dist, size=1,
        input_facts=pair.input_facts,
        base_inputs=pair.base_inputs, dist_inputs=pair.dist_inputs,
        output_specs=pair.output_specs, options=options)


def verify_model_tp(
    arch: str,
    tp: int = 16,
    *,
    smoke: bool = False,
    batch: int = 1,
    seq: int = 32,
    n_layers: Optional[int] = None,
    options: Optional[VerifyOptions] = None,
    mutate_dist=None,
) -> Report:
    """Deprecated: use ``Session().verify(arch, Plan(tp=...))``."""
    _warn("verify_model_tp", "repro.verify.Session with Plan(tp=...)")
    if tp <= 1:
        return _tp1_report(arch, decode=False, smoke=smoke, batch=batch,
                           dim2=seq, n_layers=n_layers, options=options,
                           mutate_dist=mutate_dist)
    from repro.verify import Plan

    with _session(options) as s:
        return s.verify(
            arch,
            Plan(tp=tp, layers=n_layers, batch=batch, seq=seq, smoke=smoke),
            mutate_dist=mutate_dist,
        )


def verify_decode_tp(
    arch: str,
    tp: int = 16,
    *,
    smoke: bool = False,
    batch: int = 2,
    max_len: int = 64,
    n_layers: Optional[int] = None,
    options: Optional[VerifyOptions] = None,
    mutate_dist=None,
) -> Report:
    """Deprecated: use ``Session().verify(arch, Plan.decode(tp=...))``."""
    _warn("verify_decode_tp", "repro.verify.Session with Plan.decode(tp=...)")
    if tp <= 1:
        return _tp1_report(arch, decode=True, smoke=smoke, batch=batch,
                           dim2=max_len, n_layers=n_layers, options=options,
                           mutate_dist=mutate_dist)
    from repro.verify import Plan, PlanError

    with _session(options) as s:
        try:
            return s.verify(
                arch,
                Plan.decode(tp=tp, layers=n_layers, batch=batch,
                            max_len=max_len, smoke=smoke),
                mutate_dist=mutate_dist,
            )
        except PlanError as e:
            raise ValueError(str(e)) from e


def __getattr__(name: str):
    # legacy private helpers, re-homed in repro.verify (kept importable for
    # one deprecation cycle)
    from repro.verify import pairs as _pairs
    from repro.verify import specs as _specs

    legacy = {
        "_forward_pair": _pairs._tp_forward_parts,
        "_decode_pair": _pairs._tp_decode_parts,
        "_verify_pspecs": _pairs.verify_pspecs,
        "_round_layers": _pairs.round_layers,
        "_shard_dim": _specs.shard_dim,
        "_spec_input_facts": _specs.spec_input_facts,
    }
    if name == "_stamped_pair":
        return _pairs._stamped_parts
    if name in legacy:
        return legacy[name]
    raise AttributeError(name)
