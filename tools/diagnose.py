#!/usr/bin/env python
"""Environment diagnostics (reference tools/diagnose.py: python/platform/
library versions, build flags, network checks for the PS cluster).

TPU edition: jax/device/mesh facts replace the CUDA and ps-lite sections."""
from __future__ import annotations

import os
import platform
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    print("----------Python Info----------")
    print("Version      :", platform.python_version())
    print("Compiler     :", platform.python_compiler())
    print("Build        :", platform.python_build())
    print("Arch         :", platform.machine(), platform.architecture()[0])

    print("----------System Info----------")
    print("Platform     :", platform.platform())
    print("system       :", platform.system())
    print("release      :", platform.release())

    print("----------Framework Info----------")
    try:
        import incubator_mxnet_tpu as mx
        print("incubator_mxnet_tpu:", mx.__version__,
              "at", os.path.dirname(mx.__file__))
        from incubator_mxnet_tpu import runtime
        feats = runtime.feature_list()
        on = [f.name for f in feats if f.enabled]
        print("Features     :", ", ".join(on) if on else "(none)")
    except Exception as e:
        print("incubator_mxnet_tpu import FAILED:", e)

    print("----------JAX / Device Info----------")
    try:
        import jax
        import jaxlib
        print("jax          :", jax.__version__)
        print("jaxlib       :", jaxlib.__version__)
        devs = jax.devices()
        print("device count :", len(devs))
        for d in devs[:8]:
            print(f"  [{d.id}] {d.device_kind} ({d.platform})")
        print("process      :", jax.process_index(), "/", jax.process_count())
    except Exception as e:
        print("jax probe FAILED:", e)

    print("----------Environment----------")
    for k in sorted(os.environ):
        if k.startswith(("MXTPU_", "MXNET_", "JAX_", "XLA_", "DMLC_", "TPU_")):
            print(f"{k}={os.environ[k]}")

    print("----------Declared Env Vars (util.ENV_VARS)----------")
    try:
        from incubator_mxnet_tpu.util import ENV_VARS
        width = max(len(n) for n in ENV_VARS)
        for name, spec in ENV_VARS.items():
            live = os.environ.get(name)
            live = "(unset)" if live is None else f"={live}"
            print(f"{name:<{width}} {spec.kind:<4} "
                  f"default={spec.default!r} {live}")
            print(f"{'':<{width}}      {spec.doc}")
    except Exception as e:
        print("ENV_VARS table FAILED:", e)

    print("----------Executable Cache (compile_cache)----------")
    try:
        from incubator_mxnet_tpu import compile_cache
        ds = compile_cache.disk_stats()
        if ds["dir"] is None:
            print("disk tier    : disabled (MXNET_EXEC_CACHE_DIR unset)")
        else:
            budget = ds["budget"]
            pct = (f" ({100.0 * ds['bytes'] / budget:.1f}% of "
                   f"{budget} budget)") if budget > 0 else " (unbounded)"
            print("dir          :", ds["dir"])
            print("entries      :", ds["entries"])
            print(f"occupancy    : {ds['bytes']} bytes{pct}")
        s = compile_cache.stats()
        print("mem entries  :", s["mem_entries"])
        print("counters     :",
              {k: s[k] for k in ("hits", "misses", "disk_hits",
                                 "evictions", "disk_errors", "fallbacks")})
    except Exception as e:
        print("compile_cache probe FAILED:", e)

    print("----------Kernel Autotuner (tune)----------")
    try:
        from incubator_mxnet_tpu import tune
        # importing the kernel providers registers their search spaces so
        # winners() can decode what the persistent store holds
        from incubator_mxnet_tpu.parallel import fused_conv  # noqa: F401
        s = tune.stats()
        print("counters     :",
              {k: s[k] for k in ("searches", "hits", "disk_hits",
                                 "disk_errors", "fallbacks", "cand_errors",
                                 "cand_mismatches", "cand_lost")})
        recs = tune.winners()
        if not recs:
            print("winners      : (none recorded)")
        else:
            by_dev = {}
            for rec in recs.values():
                by_dev.setdefault(rec.get("device_kind", "?"), []).append(rec)
            for dev in sorted(by_dev):
                group = by_dev[dev]
                print(f"device kind  : {dev} ({len(group)} tuned shapes)")
                for rec in sorted(group, key=lambda r: (r["kernel"],
                                                        r["key"])):
                    t = rec.get("timings_us", {})
                    best = t.get(rec["winner"])
                    best = "" if best is None else f" {best}us"
                    print(f"  {rec['kernel']:<16} -> {rec['winner']}{best}"
                          f"  [{rec['key']}]")
                    for name, why in rec["rejected"].items():
                        print(f"    rejected {name}: {why}")
    except Exception as e:
        print("tune probe FAILED:", e)

    print("----------Fault Tolerance (fault)----------")
    try:
        from incubator_mxnet_tpu import fault
        s = fault.stats()
        print("checkpoint   :",
              {k.replace("ckpt_", ""): s[k] for k in
               ("ckpt_saves", "ckpt_async_snapshots", "ckpt_dropped",
                "ckpt_errors", "ckpt_fallbacks", "ckpt_last_step")})
        print("write ms     :", round(s["ckpt_write_ms"], 1))
        print("liveness     :",
              {k: s[k] for k in ("heartbeats_sent", "dead_nodes_seen",
                                 "stragglers_seen", "rejoins",
                                 "membership_changes")})
        print("injected     :", s["faults_injected"])
        print("dead nodes   :", fault.get_dead_nodes())
    except Exception as e:
        print("fault probe FAILED:", e)

    print("----------Step Breakdown (profiler attribution)----------")
    try:
        from incubator_mxnet_tpu import profiler
        ps = profiler.phase_stats()
        print("attribution  :", "on" if profiler.attribution_enabled()
              else "off (MXNET_STEP_ATTRIBUTION unset)")
        print("steps closed :", ps["steps"], " spans:", ps["spans"])
        for phase in sorted(ps["phases"],
                            key=lambda p: -ps["phases"][p]["total_ms"]):
            row = ps["phases"][phase]
            print(f"  {phase:<14} {row['count']:>7}x "
                  f"avg {row['avg_ms']:8.3f}ms "
                  f"max {row['max_ms']:8.3f}ms")
        costs = profiler.cost_stats()
        if costs:
            print("compiler cost:")
            for key in sorted(costs):
                row = costs[key]
                gf = row.get("flops")
                inten = row.get("intensity")
                print(f"  {key:<28} "
                      + (f"{gf / 1e9:9.3f} GFLOP" if gf else "   (no flops)")
                      + (f"  {inten:8.2f} F/B" if inten else ""))
        mfu = profiler.mfu_stats()
        if mfu:
            print(f"MFU          : {mfu['mfu'] * 100:.1f}% "
                  f"({mfu['key']}, compiler cost / compute phase)")
        from incubator_mxnet_tpu import fault as _flt
        print("flight rec   :", "on -> " + os.environ.get(
            "MXNET_FLIGHT_RECORDER", "") if _flt.flight_enabled()
            else "off (MXNET_FLIGHT_RECORDER unset)")
    except Exception as e:
        print("step breakdown probe FAILED:", e)

    print("----------Fleet Observability (fleetobs)----------")
    try:
        from incubator_mxnet_tpu import fleetobs
        print("plane        :", "on" if fleetobs.enabled()
              else "off (MXNET_FLEET_OBS unset)")
        s = fleetobs.stats()
        print("snapshots    :",
              {k.replace("snapshots_", ""): s[k] for k in
               ("snapshots_built", "snapshots_skipped",
                "snapshots_folded")})
        print("slo engine   :",
              {k: s[k] for k in ("slo_evals", "alerts_raised",
                                 "alerts_resolved")})
        print("profiling    :",
              {k.replace("profile_", ""): s[k] for k in
               ("profile_requests", "profile_runs", "profile_pushes",
                "profile_fetches", "profile_bytes")})
        regs = fleetobs.registries()
        if not regs:
            print("registries   : (none live in this process)")
        for reg in regs:
            occ = reg.occupancy()
            print("registry     :",
                  {k: occ[k] for k in ("ranks", "phases",
                                       "pending_commands",
                                       "stored_profiles",
                                       "alerts_active")})
            for alert in reg.engine.active():
                print(f"  ALERT {alert['spec']} value={alert['value']} "
                      f"burn={alert['burn_short']}/{alert['burn_long']}")
            lf = occ["last_fetch"]
            if lf:
                print(f"  last fetch : rank {lf['rank']} gen {lf['gen']} "
                      f"req {lf['request_id']}")
    except Exception as e:
        print("fleetobs probe FAILED:", e)

    print("----------Control Plane (serve)----------")
    try:
        from incubator_mxnet_tpu.serve import control_plane
        from incubator_mxnet_tpu.util import getenv_int
        s = control_plane.stats()
        print("registry     :",
              {k: s[k] for k in ("registrations", "deregistrations",
                                 "beats", "graceful_shutdowns")})
        print("rollout      :",
              {k.replace("rollout_", ""): s[k] for k in
               ("rollouts_started", "rollout_waves",
                "rollout_replicas_updated", "rollout_replica_failures",
                "rollbacks")})
        print("router knobs :",
              {"deadline_ms": getenv_int("MXNET_ROUTER_DEADLINE_MS"),
               "retries": getenv_int("MXNET_ROUTER_RETRIES"),
               "hedge_delay_ms": getenv_int("MXNET_ROUTER_HEDGE_DELAY_MS"),
               "breaker_failures":
                   getenv_int("MXNET_ROUTER_BREAKER_FAILURES"),
               "breaker_cooldown_ms":
                   getenv_int("MXNET_ROUTER_BREAKER_COOLDOWN_MS")})
        print("live window  :", control_plane._live_window_s(), "s")
    except Exception as e:
        print("control plane probe FAILED:", e)

    print("----------Disaggregated Serving----------")
    try:
        from incubator_mxnet_tpu.serve import disagg
        from incubator_mxnet_tpu.util import (getenv_bool, getenv_int,
                                              getenv_str)
        print("roles        :",
              {"role": getenv_str("MXNET_DISAGG_ROLE"),
               "prefill_chunk":
                   getenv_int("MXNET_DISAGG_PREFILL_CHUNK"),
               "ship_ttl_s": getenv_int("MXNET_DISAGG_SHIP_TTL")})
        print("prefix cache :",
              {"enabled": getenv_bool("MXNET_PREFIX_CACHE"),
               "max_pages": getenv_int("MXNET_PREFIX_CACHE_PAGES")})
        s = disagg.stats()
        print("shipping     :",
              {k: s.get(k, 0) for k in ("prefill_requests", "chunks_total",
                                        "pages_shipped", "bytes_shipped",
                                        "pages_fetched", "fetch_misses")})
        # in-process probe: a tiny radix cache over a throwaway
        # allocator — exercises share/CoW/evict without any device work
        from incubator_mxnet_tpu.serve.decode import PageAllocator
        from incubator_mxnet_tpu.serve.prefix_cache import PrefixCache
        alloc = PageAllocator(8)
        cache = PrefixCache(alloc, 4, max_pages=4)
        seq = [1, 2, 3, 4, 5, 6]
        pages = alloc.alloc(2)
        cache.insert(seq, pages, len(seq))
        alloc.free(pages)
        hit_pages, covered, partial = cache.lookup(seq + [7])
        cache.lookup([9, 9, 9, 9, 9])       # miss
        cs = cache.stats()
        print("probe        :",
              {"covered": covered, "partial": partial,
               "hit_rate": cs["hit_rate"],
               "cached_pages": cs["cached_pages"]})
        alloc.free(hit_pages)
        cache.clear()
        ok = alloc.free_count == 8
        print("probe drain  :", "refcounts returned to 0" if ok
              else f"LEAKED pages ({alloc.free_count}/8 free)")
    except Exception as e:
        print("disagg probe FAILED:", e)

    print("----------Speculative Decoding----------")
    try:
        from incubator_mxnet_tpu.util import getenv_bool, getenv_int
        print("knobs        :",
              {"enabled": getenv_bool("MXNET_SPEC_DECODE"),
               "k": getenv_int("MXNET_SPEC_K"),
               "adapt": getenv_bool("MXNET_SPEC_ADAPT"),
               "accept_floor_pct":
                   getenv_int("MXNET_SPEC_ACCEPT_FLOOR_PCT")})
        print("router SLO   :",
              {"split": getenv_bool("MXNET_ROUTER_SLO_SPLIT"),
               "ttft_slo_ms": getenv_int("MXNET_ROUTER_TTFT_SLO_MS"),
               "token_slo_ms": getenv_int("MXNET_ROUTER_TOKEN_SLO_MS")})
        # in-process probe: the numpy self-draft + adaptive-k policy
        # over a throwaway toy predictor — no device work, no compiles
        from incubator_mxnet_tpu.serve.decode import DecodePredictor
        from incubator_mxnet_tpu.serve.spec_decode import SpecDecoder
        pred = DecodePredictor.toy(slots=2, page_size=4, num_pages=16,
                                   max_pages_per_seq=4,
                                   prompt_buckets=(4,))
        spec = SpecDecoder(pred, k=4)
        draft = spec.make_draft([1, 2, 3])
        drafted = draft.propose(4, 3)
        draft.sync(3, [4] + drafted[:1])        # reject 2 of 3
        print("probe        :",
              {"verify_key": spec._verify_key(),
               "drafted": len(drafted), "rows_after_sync": draft.rows,
               "k_walk": [spec.next_k(4, 0.2), spec.next_k(2, 0.95),
                          spec.next_k(3, 0.7)]})
        ok = draft.rows == 5
        print("probe sync   :", "rollback truncated to committed rows"
              if ok else f"WRONG row count ({draft.rows} != 5)")
    except Exception as e:
        print("spec decode probe FAILED:", e)

    print("----------Request Tracing----------")
    try:
        from incubator_mxnet_tpu.util import getenv_bool, getenv_int
        from incubator_mxnet_tpu.serve import reqtrace as _rt
        print("knobs        :",
              {"enabled": getenv_bool("MXNET_REQTRACE"),
               "sample_per_mille": getenv_int("MXNET_REQTRACE_SAMPLE"),
               "ring": getenv_int("MXNET_REQTRACE_RING")})
        # in-process probe: force the gate on, walk one synthetic request
        # through mint -> header roundtrip -> span -> finish, then reset
        # so the probe leaves no records behind
        _rt.enable(True)
        try:
            ctx = _rt.mint(deadline_ms=250.0)
            back = _rt.from_header(_rt.to_header(ctx))
            with _rt.activate(ctx):
                with _rt.span("router_queue"):
                    pass
            _rt.finish(ctx, status="error", cause="diagnose-probe",
                       ttft_ms=123.0, total_ms=130.0)
            snap = _rt.ring_snapshot()
            print("probe        :",
                  {"header_ok": back is not None
                   and back.trace_id == ctx.trace_id,
                   "records": _rt.record_count(),
                   "ring": {"recent": len(snap["recent"]),
                            "exemplars": len(snap["exemplars"]),
                            "capacity": snap["capacity"]}})
            print("slowest-5    :",
                  [(r["trace"][:8],
                    r.get("total_ms") or r.get("ttft_ms")
                    or r.get("elapsed_ms"))
                   for r in _rt.slowest(5)])
        finally:
            _rt.reset()
    except Exception as e:
        print("request tracing probe FAILED:", e)

    print("----------Composed Parallelism (pipeline schedules)----------")
    try:
        from incubator_mxnet_tpu.parallel.pipeline import (REMAT_MODES,
                                                           SCHEDULES,
                                                           schedule_stats)
        from incubator_mxnet_tpu.util import getenv_bool, getenv_int, \
            getenv_str
        from incubator_mxnet_tpu import profiler as _prof
        print("schedule     :", getenv_str("MXTPU_PP_SCHEDULE"),
              f"(MXTPU_PP_SCHEDULE; one of {'/'.join(SCHEDULES)})")
        print("remat        :", getenv_str("MXNET_REMAT"),
              f"(MXNET_REMAT; one of {'/'.join(REMAT_MODES)})")
        print("vstages      :", getenv_int("MXTPU_PP_VSTAGES"),
              "(MXTPU_PP_VSTAGES; interleaved chunks per rank)")
        print("offload      :", getenv_bool("MXNET_PP_OFFLOAD"),
              "(MXNET_PP_OFFLOAD; stage inputs -> pinned host)")
        print("bubble fraction by (stages, microbatches):")
        print("   S  M   gpipe   1f1b  il(v2)    zb1   "
              "live/stage(gpipe -> 1f1b)")
        for s, m in ((2, 4), (4, 8), (4, 16), (8, 32)):
            g = schedule_stats("gpipe", s, m)
            f = schedule_stats("1f1b", s, m)
            il = schedule_stats("interleaved", s, m, n_chunks=2)
            z = schedule_stats("zb1", s, m)
            print(f"  {s:2d} {m:2d}  {g['bubble_fraction']:.4f} "
                  f"{f['bubble_fraction']:.4f}  {il['bubble_fraction']:.4f} "
                  f"{z['bubble_fraction']:.4f}   "
                  f"{g['max_live_per_stage']} -> {f['max_live_per_stage']}")
        phases = _prof.last_step_phases()
        if phases.get("pp_bubble") is not None:
            print("last step    :", {k: round(v, 2)
                                     for k, v in sorted(phases.items())})
        else:
            print("last step    : no attributed pp_bubble phase recorded "
                  "(run a pp>1 step with attribution on)")
    except Exception as e:
        print("composed parallelism probe FAILED:", e)

    print("----------Static Analysis (mxlint)----------")
    try:
        from tools.mxlint import lint_paths
        pkg = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "incubator_mxnet_tpu")
        res = lint_paths([pkg])
        summary = res.as_dict()
        print("files scanned:", summary["files_scanned"])
        print("findings     :", len(summary["findings"]),
              summary["counts"] if summary["counts"] else "")
        print("suppressed   :", len(summary["suppressed"]))
        for s in summary["suppressed"]:
            print(f"  {s['rule']} {s['path']}:{s['line']} ({s['reason']})")
        for f in summary["findings"][:20]:
            print(f"  {f['rule']} {f['path']}:{f['line']} {f['message']}")
    except Exception as e:
        print("mxlint probe FAILED:", e)

    print("----------Concurrency Sanitizer (mxsan)----------")
    try:
        from incubator_mxnet_tpu import mxsan as _mx
        from incubator_mxnet_tpu.util import getenv_int, getenv_str
        from tools.mxsan import RULES as SAN_RULES
        from tools.mxsan import analyze, declared_edge_count
        from tools.mxsan.waivers import WAIVERS as SAN_WAIVERS
        from tools.mxlint.lock_order import (BLOCKING_OK,
                                             CROSS_MODULE_EDGES,
                                             LOCK_ORDER)
        print("gate         :", "on" if _mx.enabled()
              else "off (MXNET_MXSAN unset)")
        print("knobs        :",
              {"ring": getenv_int("MXNET_MXSAN_RING"),
               "log": getenv_str("MXNET_MXSAN_LOG") or "(unset)"})
        print("declared     :",
              {"modules": len(LOCK_ORDER),
               "edges": declared_edge_count(),
               "cross_module": len(CROSS_MODULE_EDGES),
               "blocking_ok": len(BLOCKING_OK)})
        print("rules        :")
        for rule, (title, _hint) in sorted(SAN_RULES.items()):
            print(f"  {rule}: {title}")
        # in-process probe: force the gate on, nest two probe locks in
        # profiler.py's declared order, and replay the witness through
        # the analyzer — a clean run proves the loop end to end; the
        # finally leaves no witness state behind
        _mx.enable(True)
        try:
            outer = _mx.lock("profiler.py", "_lock")
            inner = _mx.lock("profiler.py", "_clock")
            with outer:
                with inner:
                    pass
            wit = _mx.witness()
            res = analyze(wit, waivers=())
            print("probe        :",
                  {"records": _mx.record_count(),
                   "edges": [f"{e['a']} -> {e['b']}"
                             for e in wit["edges"]],
                   "findings": [f.key for f in res.findings] or "clean"})
        finally:
            _mx.reset()
        print("waivers      :", len(SAN_WAIVERS))
        for rule, glob, reason in SAN_WAIVERS:
            print(f"  {rule} on {glob}: {reason}")
        print("run it       : MXNET_MXSAN=1 MXNET_MXSAN_LOG=w.json "
              "<workload>; python -m tools.mxsan w.json [--format=json]")
    except Exception as e:
        print("mxsan probe FAILED:", e)

    print("----------Graph Analysis (shardlint)----------")
    try:
        from incubator_mxnet_tpu import shardlint
        from tools.shardlint import RULES
        from tools.shardlint.corpus import entries
        from tools.shardlint.waivers import WAIVERS
        s = shardlint.stats()
        print("capture      :", "on" if s["enabled"] else
              "off (MXNET_SHARDLINT unset)")
        print("counters     :",
              {k: s[k] for k in ("captures", "jit", "tuned",
                                 "partition", "dropped")})
        print("rules        :")
        for rule, (title, _hint) in sorted(RULES.items()):
            print(f"  {rule}: {title}")
        print("corpus       :", ", ".join(entries()))
        print("waivers      :", len(WAIVERS))
        for rule, glob, reason in WAIVERS:
            print(f"  {rule} on {glob}: {reason}")
        print("run it       : python -m tools.shardlint [--format=json]")
    except Exception as e:
        print("shardlint probe FAILED:", e)
    return 0


if __name__ == "__main__":
    sys.exit(main())
