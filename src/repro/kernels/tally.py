"""Dispatch tally, decline exception + interpret-mode env accessor.

This module deliberately imports nothing heavy (no jax, no numpy): the
engines in ``core/`` import it at module load, while ``kernels.ops``
(which imports ``core``) is only imported lazily at dispatch time.
(``repro.obs.registry`` is pure stdlib, so depending on it keeps that
property.)

``KernelDeclined`` is the dispatcher's own decline — no TPU and no
interpret-mode request, or a segment brick beyond the admission bound.
It is the only exception the graceful-degradation arms in ``core/``
catch: a Pallas lowering or Mosaic compile error on the chip is a bug,
and it propagates instead of rerouting onto the XLA scan.

``KERNEL_CALLS`` tallies host-side kernel dispatches per kind ("a1",
"a1_state", "a1_mapc", "a1_mapc_shard", the "a2"/"a2_*" analogues) and
every graceful degradation under a ``fallback:<site>`` kind
(``record_fallback``).  A downgrade that does not move a tally is
invisible to both the service telemetry and the contract auditor
(``repro.analysis``); the auditor's KC105 rule rejects a degradation arm
that catches more than ``KernelDeclined`` or does not call
``record_fallback``.

Since the obs PR the tally is a *view* over the process-global metrics
registry: ``KERNEL_CALLS[kind]`` reads/writes the
``kernel_calls{kind=...}`` counter family in ``repro.obs.REGISTRY``, so
the audit artifact (``dict(KERNEL_CALLS)``), the service health snapshot,
and exported metrics are one set of numbers that cannot drift.  Audit
rule KC107 rejects any shadow tally or direct ``fallback:`` write outside
this accessor module.

``interpret_requested`` is the single accessor for the
``REPRO_KERNEL_INTERPRET`` / ``REPRO_INTERPRET_KERNELS`` environment
aliases (both spellings remain accepted; earlier PRs read them
inconsistently from two call sites).  The auditor's KC106 rule rejects
direct ``os.environ`` reads of either name anywhere else.
"""

from __future__ import annotations

import os
from collections.abc import MutableMapping

from repro.obs.registry import REGISTRY

# Accepted spellings for "run the Pallas kernels in interpret mode".
# REPRO_KERNEL_INTERPRET is the documented name; the other is a legacy
# alias kept so existing CI configs and scripts don't break.
INTERPRET_ENV_VARS = ("REPRO_KERNEL_INTERPRET", "REPRO_INTERPRET_KERNELS")

_FAMILY = "kernel_calls"


class KernelDeclined(NotImplementedError):
    """The kernel dispatcher declined this launch (no TPU and no
    interpret-mode request, or an unadmitted segment brick); the caller
    reroutes onto its XLA engine and calls ``record_fallback``."""


class _KernelCallsView(MutableMapping):
    """``collections.Counter``-compatible view over the registry's
    ``kernel_calls`` family.

    Supports everything the codebase and tests do with the old Counter:
    ``KERNEL_CALLS[k] += n`` (missing keys read as 0), ``dict(...)``,
    ``.items()``, ``.clear()``, comparisons against ints. Iteration
    yields only kinds that have been touched, like a Counter that never
    stored zero-count keys."""

    def __getitem__(self, kind: str) -> int:
        for labels, m in REGISTRY.family_items(_FAMILY):
            if labels.get("kind") == kind:
                return m.value
        return 0

    def __setitem__(self, kind: str, value: int) -> None:
        REGISTRY.counter(_FAMILY, kind=kind)._force_set(value)

    def __delitem__(self, kind: str) -> None:
        REGISTRY.counter(_FAMILY, kind=kind)._force_set(0)

    def __iter__(self):
        return iter([labels["kind"]
                     for labels, _ in REGISTRY.family_items(_FAMILY)])

    def __len__(self) -> int:
        return len(REGISTRY.family_items(_FAMILY))

    def clear(self) -> None:
        REGISTRY.clear_family(_FAMILY)

    def __repr__(self) -> str:
        return f"KERNEL_CALLS({dict(self)})"


KERNEL_CALLS = _KernelCallsView()


def reset_kernel_calls() -> None:
    """Zero the dispatch tally (test / audit instrumentation)."""
    KERNEL_CALLS.clear()


def record_fallback(site: str) -> None:
    """Record one kernel→XLA graceful degradation at ``site``.

    Every ``except KernelDeclined`` arm that reroutes a kernel dispatch
    onto an XLA engine must call this, so downgrades
    show up in the same tally the kernel dispatches do —
    ``KERNEL_CALLS["fallback:<site>"]``. Enforced by
    ``repro.analysis.contracts`` rule KC105; writing the ``fallback:``
    kind anywhere else is a KC107 violation.
    """
    REGISTRY.counter(_FAMILY, kind="fallback:" + site).inc()


def fallback_counts() -> dict:
    """The ``fallback:*`` slice of the tally (site → count)."""
    return {k.split(":", 1)[1]: v for k, v in KERNEL_CALLS.items()
            if k.startswith("fallback:")}


def interpret_requested() -> bool:
    """Whether the environment asks for interpret-mode kernels.

    Single source of truth for the ``REPRO_KERNEL_INTERPRET`` /
    ``REPRO_INTERPRET_KERNELS`` aliases — read the env through this
    accessor only (audit rule KC106)."""
    return any(os.environ.get(v) == "1" for v in INTERPRET_ENV_VARS)
