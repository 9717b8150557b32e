#!/usr/bin/env python3
"""A run of the benchmark with the timed path broken underneath.

  python3 bench/tests/faulty_run.py <fault> <bench/run.py arguments>

Faults, each planted in the program before the run starts:

  state_unchanged  the carried counting kernels return their state as
                   they got it (no window advances any machine)
  half_batch       the A1 kernel leaves every other episode lane out: its
                   count stays where it was
  altered_answer   the first multi-node episode of every delta reports its
                   count plus one, where the delta is produced
  duplicate_delta  the client hands the first delta it received over a
                   second time, with the next poll's
  control          the plain reference's guarantee broken the way a cheaper
                   counter would: bounded lists of two slots per level and no
                   exact recount of the episodes whose lists overflowed
  no_recount       the configuration's own bounded lists, with that exact
                   recount switched off

Every one of them has to make the run report ``"correct": false``, on
traffic that can show it: ``no_recount`` only where the lists overflow,
as in bursts (``bench/tests/test_bench_bursts.py``). The control runs on
the chip too, at the cell's size (``PERF.md``).
"""

import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import run  # noqa: E402


def plant(fault: str) -> None:
    import dataclasses

    import jax.numpy as jnp

    from repro.core import streaming
    from repro.kernels import ops
    from repro.service import session

    if fault == "state_unchanged":
        ops.a1_state_call = lambda et, tlo, thi, ev, s, po, cnt, ovf, **kw: (
            cnt, ovf, s, po)
        ops.a2_state_call = lambda et, tlo, thi, ev, s, cnt, **kw: (cnt, s)
    elif fault == "half_batch":
        real = ops.a1_state_call

        def half(et, tlo, thi, ev, s, po, cnt, ovf, **kw):
            before = jnp.array(cnt)  # the call donates its state
            c, o, s2, p2 = real(et, tlo, thi, ev, s, po, cnt, ovf, **kw)
            odd = jnp.arange(c.shape[-1]) % 2 == 1
            return jnp.where(odd, before, c), o, s2, p2
        ops.a1_state_call = half
    elif fault == "altered_answer":
        real = session.WindowDelta.episodes

        def altered(self, level=None):
            out = real(self, level)
            for i, (et, c) in enumerate(out):
                if len(et) > 1:
                    out[i] = (et, c + 1)
                    break
            return out
        session.WindowDelta.episodes = altered
    elif fault == "duplicate_delta":
        from repro.service.client import MiningClient

        real = MiningClient.poll
        state = {}

        def twice(self, ack=True):
            out = real(self, ack)
            if out and "first" not in state:
                state["first"] = out[0]
            elif out and not state.get("again"):
                state["again"] = True
                out.append(state["first"])
            return out
        MiningClient.poll = twice
    elif fault in ("no_recount", "control"):
        streaming.StreamingCounter._restore_exact_bounded = (
            lambda self, c, flagged: c)
        if fault == "control":
            real = run.session_config
            run.session_config = lambda cfg, traffic: dataclasses.replace(
                real(cfg, traffic), lcap=2)
            if "--rehearse" in sys.argv:
                # two slots overflow only at the configuration's own rates: keep
                # them in the rehearsal, counted by the XLA scans (the same
                # bounded-list machines as the kernels, far faster on the CPU)
                os.environ.pop("REPRO_KERNEL_INTERPRET", None)
                scaled = run.scaled
                run.scaled = lambda cell, rehearse: (
                    cell.config, {**scaled(cell, rehearse)[1], "warmup_windows": 1})
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    fault, argv = sys.argv[1], sys.argv[2:]
    run.prepare("--rehearse" in argv)
    plant(fault)
    sys.exit(run.main(argv))
