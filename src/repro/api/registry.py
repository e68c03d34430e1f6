"""String registry for placement policies (and estimator shorthands).

The registry maps names to zero-argument factories so that configuration
surfaces (CLI flags, benchmark tables, YAML) can name policies without
importing their classes:

    @register_policy("my-policy")
    class MyPolicy: ...

    # or, for parameterized variants:
    register_policy("my-policy-tight", lambda: MyPolicy(headroom=0.3))

    policy = get_policy("my-policy")

``resolve_policy`` additionally passes an already-constructed policy
object through, so the simulator and the serving engine take a policy by
its registry name or as an object, and by nothing else.
"""
from __future__ import annotations

from typing import Callable, Dict, List

_POLICIES: Dict[str, Callable[[], object]] = {}


def register_policy(name: str, factory: Callable[[], object] | None = None):
    """Register a policy factory under ``name``.

    Usable as a decorator on a policy class (zero-arg constructible) or
    called directly with a factory/lambda.

    Duplicate names: re-registering an existing name silently OVERWRITES
    the previous factory — last registration wins, with no error or
    warning.  This is deliberate: ``importlib.reload`` / notebook re-runs
    re-execute the decorators, and raising on the second pass would make
    iterative development impossible.  The flip side is that a typo'd
    name can shadow a built-in (e.g. re-registering ``"flex-f"``), so
    pick distinct names for experiments; ``list_policies()`` shows what
    is currently live, and the docs-drift check (``scripts/check_docs.py``,
    run as part of tier-1) fails when a registered name is missing from
    the ``docs/api.md`` registry table.

    ``get_policy(name)`` calls the factory on EVERY lookup, so callers
    receive a fresh instance each time — registered classes must be
    cheap, zero-argument constructibles (frozen dataclasses with
    defaults).
    """
    def _add(f):
        _POLICIES[name] = f
        return f

    if factory is None:
        return _add
    return _add(factory)


def _ensure_builtins():
    # Built-in policies live in repro.api.policies; importing it populates
    # the registry.  Lazy to keep registry import-light and cycle-free.
    import repro.api.policies  # noqa: F401


def get_policy(name: str):
    """Instantiate the policy registered under ``name``."""
    _ensure_builtins()
    try:
        return _POLICIES[name]()
    except KeyError:
        raise KeyError(
            f"unknown policy {name!r}; registered: {sorted(_POLICIES)}"
        ) from None


def list_policies() -> List[str]:
    _ensure_builtins()
    return sorted(_POLICIES)


def resolve_policy(policy):
    """str | PlacementPolicy -> PlacementPolicy; unknown names raise
    ``KeyError`` listing the registered ones."""
    if isinstance(policy, str):
        return get_policy(policy)
    return policy
