"""Worker process for ``perf_lab --exp chaos_restart``.

The parent never touches JAX: a device belongs to one process at a time,
so every run that needs one happens here, one process after another.

    restart_child.py baseline <scenario> <smoke> <out.json>
    restart_child.py kill     <scenario> <smoke> <snapshot_dir> <kill_at>
    restart_child.py resume   <scenario> <smoke> <snapshot_dir> <out.json>
                              [<expert>x<model> resume mesh]

``baseline`` serves the seeded stream uninterrupted and writes every
request's (status, tokens).  ``kill`` serves it with per-chunk snapshots
and ``SIGKILL``s its own process — no atexit handler, no buffered flush,
no __del__ runs — from a chunk hook at ``kill_at``; it exits 3 if the run
completes without being killed (kill_at was past the end of the
workload) so the parent can tell that from a crash.  ``resume`` rebuilds
the killed run from the snapshot directory (optionally onto a different
mesh shape) and writes the resumed tokens and the recovery plan.
"""

import json
import os
import signal
import sys


def main() -> int:
    mode, scenario, smoke = sys.argv[1:4]
    smoke = bool(int(smoke))
    rest = sys.argv[4:]

    # Env BEFORE jax (via perf_lab) imports: the mesh scenario needs 8
    # forced host devices, everything else runs single-device.
    ndev = 8 if "mesh" in scenario else 1
    os.environ["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={ndev}"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    from benchmarks.perf_lab import _restart_setup

    from repro import api as capi
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    def tokens(reqs):
        return {str(r.uid): [r.status, [int(t) for t in r.out_tokens]]
                for r in reqs}

    if mode == "baseline":
        api, rt, base, reg, mk_reqs, engine_kw = _restart_setup(scenario,
                                                                smoke)
        reqs = mk_reqs()
        capi.serve(api, rt, base, reg, **engine_kw).run(reqs)
        with open(rest[0], "w") as f:
            json.dump({"tokens": tokens(reqs)}, f)
        return 0

    if mode == "kill":
        snap_dir, kill_at = rest[0], int(rest[1])
        api, rt, base, reg, mk_reqs, engine_kw = _restart_setup(scenario,
                                                                smoke)
        eng = capi.serve(api, rt, base, reg, snapshot_dir=snap_dir,
                         snapshot_every_chunks=1, **engine_kw)

        def die(i):
            if i == kill_at:
                os.kill(os.getpid(), signal.SIGKILL)

        eng.chunk_hooks.append(die)
        eng.run(mk_reqs())
        return 3          # survived: kill_at never fired

    if mode == "resume":
        snap_dir, out = rest[0], rest[1]
        mesh_shape = (tuple(int(v) for v in rest[2].split("x"))
                      if len(rest) > 2 else None)
        api, rt, base, reg, mk_reqs, engine_kw = _restart_setup(
            scenario, smoke, mesh_shape=mesh_shape)
        eng = capi.serve(api, rt, base, reg, snapshot_dir=snap_dir,
                         snapshot_every_chunks=1, **engine_kw)
        reqs = eng.resume()
        stats = eng.recovery_stats
        with open(out, "w") as f:
            json.dump({"tokens": tokens(reqs),
                       "resume_seconds": stats["resume_seconds"],
                       "first_resumed_token_s":
                           stats.get("first_resumed_token_s"),
                       "plan": stats["plan"].as_dict()}, f)
        return 0

    raise SystemExit(f"unknown mode {mode!r}")


if __name__ == "__main__":
    sys.exit(main())
