"""QRD-RLS fleet serving launcher — the deployment entrypoint.

    PYTHONPATH=src python -m repro.launch.serve --preset equalizer-ieee \
        [--slots 131072] [--cohorts 4] [--steps 1000] [--devices K] \
        [--ckpt-dir DIR] [--config cfg.json] [--seed 0]

Brings up an `repro.serve.RLSFleet` + `FleetServer` from a named preset
(`repro.serve.presets`) or a ``QRDConfig.to_json`` file, admits
`--cohorts` equal cohorts filling the fleet, then drives `--steps`
synthetic-traffic ticks (`repro.data.pipeline.SyntheticTraffic`) through
the async snapshot queue — submit, pump, heartbeat — with a checkpoint
every `--ckpt-every` steps when `--ckpt-dir` is set, and prints the
health report and sustained update throughput at the end.

``--devices K`` shards the slot axis across a (K, 1) data mesh via
`launch.sharding.shard_fleet`: K real chips on a TPU host (fewer is an
error), or K faked host devices on the CPU
(``--xla_force_host_platform_device_count``).  `serve` is the same
path as a function (``chip_smoke.py`` drives it on the chip).

Exit code 0 requires every submitted snapshot to be applied (no backlog,
nothing dropped) and, when checkpointing, a final evict → restore that
reproduces the served weights bit-exactly — this is what CI's
serve-smoke lane asserts at the 2^17-slot scale.
"""
import argparse
import os
import time

import numpy as np


def device_mesh(devices: int):
    """A (K, 1) ("data", "model") mesh over the first K default devices.

    On a TPU host these are K real chips, and asking for more than exist
    fails.  On the CPU they are the K host devices that
    ``--xla_force_host_platform_device_count`` (set by `main` before JAX
    starts) fakes — that flag only ever shapes the CPU platform.
    """
    import jax
    avail = jax.devices()
    if len(avail) < devices:
        raise SystemExit(
            f"--devices {devices}: only {len(avail)} "
            f"{avail[0].platform} device(s) on this host")
    return jax.sharding.Mesh(np.asarray(avail[:devices]).reshape(devices, 1),
                             ("data", "model"))


def serve(preset="equalizer-float64", *, config=None, slots=0, n=0,
          cohorts=4, steps=200, per_step=0, mesh=None, ckpt_dir=None,
          ckpt_every=100, seed=0, log=print):
    """Bring up a preset's fleet and server and drive synthetic traffic.

    The body of ``python -m repro.launch.serve`` (arguments as its
    options; ``config`` a `QRDConfig` overriding the preset's, ``mesh``
    the slot-axis mesh).  Admits ``cohorts`` equal cohorts filling the
    fleet, then serves ``steps`` traffic ticks — submit, pump, heartbeat
    — checkpointing every ``ckpt_every`` steps when ``ckpt_dir`` is set.
    With a ``ckpt_dir`` it ends with evict → restore of the first cohort
    and checks that the served weights come back bit-exactly.

    Returns a dict: ``server``, ``fleet``, ``traffic``, ``ticks`` (the
    ``(cohort, users, x, d)`` submitted per tick, host arrays),
    ``applied``, ``elapsed`` (seconds of the serving loop) and
    ``failures`` (empty when every snapshot was applied and, with
    checkpoints, the restore reproduced the weights).
    """
    from repro.data.pipeline import SyntheticTraffic
    from repro.qrd import QRDEngine
    from repro.serve import FleetServer, fleet_preset

    spec = fleet_preset(preset)
    cfg = config or spec["config"]
    fleet_kw = spec["fleet"]
    if slots:
        fleet_kw["slots"] = slots
    if n:
        fleet_kw["n"] = n

    log(f"preset {preset}: {spec['description']}")
    log(f"config: {cfg.to_json()}")
    t0 = time.perf_counter()
    fleet = QRDEngine(cfg).fleet(mesh=mesh, **fleet_kw)
    server = FleetServer(fleet, ckpt_dir=ckpt_dir, **spec["server"])
    size = fleet.slots // cohorts
    for c in range(cohorts):
        server.admit_cohort(
            f"cohort-{c}", size if c else fleet.slots - size * (cohorts - 1))
    log(f"bring-up: {fleet!r} in {time.perf_counter() - t0:.2f}s, "
        f"{cohorts} cohorts of ~{size}")

    per_step = per_step or server.batch
    names = [c.name for c in server.cohorts()]
    traffic = SyntheticTraffic(users=min(c.size for c in server.cohorts()),
                               n=fleet.n, per_step=per_step, seed=seed,
                               complex_dtype=fleet.is_complex)
    ticks, applied = [], 0
    t0 = time.perf_counter()
    for step in range(steps):
        tick = traffic.batch(step)
        name = names[step % len(names)]
        users, X, d = (np.asarray(tick[k]) for k in ("user", "x", "d"))
        ticks.append((name, users, X, d))
        server.submit_batch(name, users, X, d)
        applied += server.pump()
        if ckpt_dir and (step + 1) % ckpt_every == 0:
            server.checkpoint()
    elapsed = time.perf_counter() - t0
    health = server.health()
    log(f"served {applied} snapshot updates over {server.step} batches "
        f"in {elapsed:.2f}s ({applied / elapsed:,.0f} updates/s)")
    for name, stats in health["cohorts"].items():
        log(f"  {name}: {stats}")

    failures = []
    if health["queue_depth"] or any(
            s["backlog"] or s["dropped_stale"] or s["dropped_overflow"]
            for s in health["cohorts"].values()):
        failures.append(f"unserved traffic: {health}")

    if ckpt_dir:
        server.checkpoint(wait=True)
        probe = names[0]
        members = np.arange(min(8, size))
        w_before = server.query(probe, members)
        server.evict_cohort(probe)           # exercise slot recycling ...
        restored = server.restore_latest()   # ... then roll everything back
        w_after = server.query(probe, members)
        if restored is None or not np.array_equal(w_before, w_after):
            failures.append("restore did not reproduce served weights")
        else:
            log(f"checkpoint/restore at step {restored}: weights "
                "bit-identical")
    return dict(server=server, fleet=fleet, traffic=traffic, ticks=ticks,
                applied=applied, elapsed=elapsed, failures=failures)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="equalizer-float64",
                    help="named deployment (repro.serve.list_fleet_presets)")
    ap.add_argument("--config", default=None,
                    help="QRDConfig JSON file (overrides the preset's config)")
    ap.add_argument("--slots", type=int, default=0,
                    help="fleet capacity (0 = preset default)")
    ap.add_argument("--n", type=int, default=0,
                    help="filter length (0 = preset default)")
    ap.add_argument("--cohorts", type=int, default=4)
    ap.add_argument("--steps", type=int, default=200,
                    help="traffic ticks to serve")
    ap.add_argument("--per-step", type=int, default=0,
                    help="snapshots per tick (0 = server batch size)")
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.devices:   # shapes the CPU platform only; see device_mesh
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count="
                                   f"{args.devices}")
    from repro.launch.cache import enable_compile_cache
    from repro.qrd import QRDConfig
    enable_compile_cache()

    config = None
    if args.config:
        with open(args.config) as f:
            config = QRDConfig.from_json(f.read())
    out = serve(args.preset, config=config, slots=args.slots, n=args.n,
                cohorts=args.cohorts, steps=args.steps,
                per_step=args.per_step,
                mesh=device_mesh(args.devices) if args.devices else None,
                ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                seed=args.seed)
    if out["failures"]:
        raise SystemExit("; ".join(out["failures"]))
    print("serve smoke OK")


if __name__ == "__main__":
    main()
