"""Sharding rules: parameter-name patterns → PartitionSpec.

The reference's model parallelism was coarse device placement (Symbol
group2ctx + the PlaceDevice pass); tensor parallelism did not exist in
MXNet 1.x (SURVEY §2.3). Here TP layouts are data: an ordered rule list
`(regex, PartitionSpec)`, first match wins, default replicate. Megatron
conventions: column-parallel weights shard the output dim on "tp",
row-parallel shard the input dim.
"""

from __future__ import annotations

import re
from typing import Iterable, List, Optional, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec

__all__ = ["ShardingRules", "PartitionSpec"]


class ShardingRules:
    """Ordered (pattern → PartitionSpec) mapping for parameter pytrees."""

    def __init__(self, rules: Optional[Iterable[Tuple[str, PartitionSpec]]]
                 = None):
        self._rules: List[Tuple[re.Pattern, PartitionSpec]] = [
            (re.compile(pat), spec) for pat, spec in (rules or [])]

    def add(self, pattern: str, spec: PartitionSpec):
        self._rules.append((re.compile(pattern), spec))
        return self

    def extend(self, other: "ShardingRules"):
        """Append another ruleset's rules (lower precedence — earlier
        rules win in spec_for's first-match scan)."""
        self._rules.extend(other._rules)
        return self

    def iter_rules(self) -> List[Tuple[str, PartitionSpec]]:
        """Ordered (pattern_string, spec) view of the rule list, for
        introspection and mxtpu.analysis.check_sharding."""
        return [(pat.pattern, spec) for pat, spec in self._rules]

    def axes(self) -> Tuple[str, ...]:
        """The mesh axes these rules shard parameters over, in first-use
        order — the model-parallel axes of the layout."""
        seen: List[str] = []
        for _, spec in self._rules:
            for entry in spec:
                for axis in ((entry,) if isinstance(entry, str)
                             else entry or ()):
                    if axis not in seen:
                        seen.append(axis)
        return tuple(seen)

    def first_match(self, name: str):
        """Index of the winning rule for `name` (first-match scan), or
        None when the name falls through to the replicate default."""
        for i, (pat, _) in enumerate(self._rules):
            if pat.search(name):
                return i
        return None

    def __len__(self):
        return len(self._rules)

    def spec_for(self, name: str, ndim: int) -> PartitionSpec:
        for pat, spec in self._rules:
            if pat.search(name):
                if len(spec) > ndim:
                    raise ValueError(
                        f"rule {pat.pattern} spec {spec} has more axes than "
                        f"param {name} (ndim={ndim})")
                return spec
        return PartitionSpec()  # replicate

    def sharding_for(self, name: str, ndim: int, mesh) -> NamedSharding:
        jm = getattr(mesh, "jax_mesh", mesh)
        return NamedSharding(jm, self.spec_for(name, ndim))

    def shard_params(self, named_arrays: dict, mesh) -> dict:
        """device_put every array to its rule's NamedSharding."""
        out = {}
        for name, arr in named_arrays.items():
            out[name] = jax.device_put(
                arr, self.sharding_for(name, arr.ndim, mesh))
        return out

    def __repr__(self):
        return "ShardingRules(%s)" % ", ".join(
            f"{p.pattern!r}→{s}" for p, s in self._rules)
