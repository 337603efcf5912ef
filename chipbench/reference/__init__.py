"""Plain references of the benchmark's configurations, one module per
architecture; a configuration file names its module under "reference".

The harness knows an architecture only through its module, so a
configuration of a new architecture joins the benchmark as new files: its
configuration, its module, its traffic and its readers. A module gives:

``param_specs(arch) -> (superblock, tail)``
    The program's parameter tree of one super-block (the layers of one
    period of ``block_pattern``, stacked over the super-blocks) and of the
    tail (the layers a depth that is no multiple of the period leaves; {}
    if none), each leaf an init spec that ``weights.make_params`` draws.
    Embedding, final norm and untied head are every token model's and are
    made there.
``projection_weights(arch) -> int``
    The weights one token meets in every projection and the head.
``mixer_flops(arch, start, count) -> float``
    Model FLOPs of ``count`` tokens at positions ``start`` onwards beyond
    the projections (attention's score and value products, a state
    space's update and readout), forward only.
``call_widths(arch) -> list``
    Output widths of the configuration's GR-MAC calls.
``STATE_KINDS``
    {block kind: "positional" | "recurrent"}: how a layer group of the
    program's cache is kept and replayed (``replay``). A positional state
    (keys and values) is written once a position, so a lane's state when
    it is handed on holds every position it wrote. A recurrent state is
    overwritten by each token, so the store also keeps it after every
    prefill chunk.
``Model(spec, params)``
    ``init_cache(batch, ctx)`` -> {group: {leaf: (layers, batch, ...)}}
    for every group of ``cache_groups(arch)``; ``step(kind, numerics,
    seq)`` -> a jitted ``fn(weights, cache, toks, index, lane_arg, served,
    given)`` -> (new cache, (top, argmax, logits of ``served``)) that
    replays one dispatch; ``weights``.
"""
from __future__ import annotations

import importlib

REQUIRED = ("param_specs", "projection_weights", "mixer_flops",
            "call_widths", "STATE_KINDS", "Model")


def module(spec: dict):
    """The configuration's reference module; refused, by name, where it
    lacks one of ``REQUIRED``."""
    name = spec["reference"]
    mod = importlib.import_module(f"chipbench.reference.{name}")
    missing = [f for f in REQUIRED if not hasattr(mod, f)]
    if missing:
        raise TypeError(f"reference module {name!r} lacks "
                        f"{', '.join(missing)}")
    return mod


def load_model(spec: dict, params: dict):
    return module(spec).Model(spec, params)


def cache_groups(arch: dict) -> list:
    """(group, block kind, layers) of every layer group of the program's
    cache, in the program's naming: one group per entry of the pattern,
    stacked over the super-blocks (``b<i>_<kind>``), then one per tail
    layer (``t<i>_<kind>``)."""
    pat = list(arch["block_pattern"])
    n_super, n_tail = divmod(arch["n_layers"], len(pat))
    out = [(f"b{i}_{k}", k, n_super) for i, k in enumerate(pat)] \
        if n_super else []
    return out + [(f"t{i}_{pat[i]}", pat[i], 1) for i in range(n_tail)]


def state_kinds(spec: dict) -> dict:
    """{group: "positional" | "recurrent"} of the configuration's cache."""
    kinds = module(spec).STATE_KINDS
    out = {}
    for group, kind, _ in cache_groups(spec["arch"]):
        if kind not in kinds:
            raise KeyError(f"reference module {spec['reference']!r} keeps "
                           f"no state of {kind!r} layers ({group})")
        out[group] = kinds[kind]
    return out
