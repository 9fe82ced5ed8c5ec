"""Device-only per-wake cost: K decremental wakes chained in one program.

wake_bench.py measures the end-to-end wake, which pays one host
round-trip per value readback — a fixed cost a small per-wake time
drowns in.  This probe pre-stages K wakes of churn as
device arrays (flag/recv scatters, layout mask scatters, suspect/fresh
words, xla-tier pair snapshots), scans the raw wake function over them
inside ONE jitted program, and times chain(K) against chain(2): the
difference divided by K-2 cancels the fixed dispatch and readback
cost, leaving the device per-wake time — the number the
<=10ms BASELINE target is judged against.

Per wake: half removals of live base pairs (masked in-layout + suspect
words), half fresh inserts (riding an xla tier whose cumulative per-wake
snapshot is pre-staged), plus a batch of flag/recv scatters (halts,
busy toggles, recv drains — the seed-churn suspects).  The final chain
state is cross-checked against the numpy oracle.

``--mode`` selects the repair fixpoint's propagation strategy
(uigc.crgc.trace-mode: push/pull/jump/auto).  Jump modes stage per-wake
jump-parent maintenance writes alongside the churn (minimum-fold on
insert, invalidate-on-remove — exactly the IncrementalPallasLayout
rules), so the chain exercises the production invariant that a pointer
never outlives the pair it was built from.  A stats replay (the same
staged wakes run unchained; the wake fn counts its own sweeps) reports the
per-wake repair sweep counts next to the chain figure, and ``--json``
dumps the whole result as a BENCH_WAKE-style artifact so the
sweep-count reduction is regression-tracked.

Usage: python tools/wake_chain_bench.py [--actors N] [--wakes 16]
       [--churn 20000] [--small] [--mode auto] [--json PATH]
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--actors", type=int, default=None)
    ap.add_argument("--wakes", type=int, default=16)
    ap.add_argument("--churn", type=int, default=20_000)
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--no-oracle", action="store_true")
    ap.add_argument(
        "--mode", default="auto",
        choices=["auto", "push", "pull", "jump"],
        help="repair-fixpoint propagation strategy (uigc.crgc.trace-mode)",
    )
    ap.add_argument(
        "--no-stats", action="store_true",
        help="skip the per-wake sweep-count replay",
    )
    ap.add_argument("--json", default=None, help="dump the result JSON here")
    args = ap.parse_args()
    if args.wakes < 3:
        ap.error("--wakes must be >= 3 (chain(2) is the baseline)")

    import jax
    import jax.numpy as jnp

    from uigc_tpu.models import powerlaw_actor_graph
    from uigc_tpu.ops import pallas_decremental as pdec
    from uigc_tpu.ops import pallas_trace as pt
    from uigc_tpu.ops import trace as trace_ops
    from uigc_tpu.utils.platform import enable_compile_cache, is_tpu_platform

    enable_compile_cache()
    platform = jax.devices()[0].platform
    on_tpu = is_tpu_platform(platform)
    n = args.actors or (10_000_000 if on_tpu and not args.small else 1 << 16)
    K = args.wakes
    churn = args.churn if not args.small else min(args.churn, 512)

    rng = np.random.default_rng(11)
    graph = powerlaw_actor_graph(n, seed=0, garbage_fraction=0.5)
    flags0 = graph["flags"]
    recv0 = graph["recv_count"]

    # --- static base layout (no pow2 padding: fixed geometry) -------- #
    from uigc_tpu.ops.pallas_incremental import IncrementalPallasLayout

    psrc, pdst, kinds = IncrementalPallasLayout.pairs_from_graph(
        graph["edge_src"], graph["edge_dst"], graph["edge_weight"],
        graph["supervisor"],
    )
    t0 = time.perf_counter()
    prep = pt.prepare_pairs(psrc, pdst, n, want_slots=True)
    pack_s = time.perf_counter() - t0
    slot_ri = prep.pop("slot_ri")
    slot_col = prep.pop("slot_col")
    r_rows = prep["r_rows"]
    n_words_pad = r_rows * pt.LANE

    # the xla tier accumulates every insert across the chain
    cap = 1 << max(10, int(K * churn // 2 - 1).bit_length())
    xla = pt.xla_tier([], [], n, cap)
    specs = (pt.layout_spec(prep), pt.layout_spec(xla))
    mode = args.mode
    use_jump = mode in (pt.MODE_JUMP, pt.MODE_AUTO)
    wake_raw = pdec.get_wake_fn(
        n, specs, prep["n_super"], r_rows, prep["s_rows"], mode=mode
    ).raw

    # --- pre-stage K wakes of churn ---------------------------------- #
    d_half, i_half = churn // 2, churn // 2
    removable = np.nonzero(kinds == 0)[0]
    removed = np.zeros(psrc.size, bool)
    # membership via a sorted packed-key array: a Python set of ~30M
    # tuples would cost GBs of host RAM at the 10M-actor default
    base_sorted = np.sort((psrc << 32) | pdst)
    new_keys: set = set()
    ins_pairs: list = []

    f_churn = max(16, churn // 8)
    flag_slots = np.full((K, f_churn), n, np.int32)  # pad = dropped
    flag_vals = np.zeros((K, f_churn), np.uint8)
    recv_slots = np.full((K, f_churn), n, np.int32)
    recv_vals = np.zeros((K, f_churn), np.int64)
    mask_rows = np.full((K, d_half), prep["row_pos"].shape[0], np.int32)
    mask_cols = np.zeros((K, d_half), np.int32)
    del_words = np.zeros((K, r_rows, pt.LANE), np.uint32)
    fresh_words = np.zeros((K, r_rows, pt.LANE), np.uint32)
    xsrc = np.full((K, cap), n, np.int32)
    xdst = np.full((K, cap), n, np.int32)
    # per-wake jump-parent writes (dst -> final value after the wake's
    # removals invalidate + inserts min-fold); pad index n+2 is OOB of
    # the (n+1,) parent array, so .set(mode="drop") ignores it
    jp_now = pt.jump_parents(psrc, pdst, n) if use_jump else None
    jp0 = jp_now.copy() if use_jump else np.zeros(1, np.int32)
    jw_idx = np.full((K, churn), n + 2, np.int32)
    jw_val = np.zeros((K, churn), np.int32)

    def set_bits(words, ids):
        ids = np.asarray(ids, np.int64)
        if ids.size:
            flat = words.reshape(-1)
            np.bitwise_or.at(
                flat, ids >> 5, np.uint32(1) << (ids & 31).astype(np.uint32)
            )

    F = trace_ops
    flags_now = flags0.copy()
    recv_now = recv0.copy()
    n_ins_total = 0
    for k in range(K):
        # flag/recv churn: halts, busy toggles, recv drains/arrivals.
        # Staged per-wake as dicts so duplicate slots keep only the LAST
        # value — .at[].set with repeated indices applies in undefined
        # order on device, which would diverge from the host truth.
        f_updates: dict = {}
        r_updates: dict = {}
        for _ in range(f_churn):
            i = int(rng.integers(0, n))
            r = rng.random()
            if r < 0.3:
                flags_now[i] |= F.FLAG_HALTED
                f_updates[i] = flags_now[i]
            elif r < 0.7:
                flags_now[i] ^= F.FLAG_BUSY
                f_updates[i] = flags_now[i]
            else:
                recv_now[i] = 0 if recv_now[i] else 2
                r_updates[i] = recv_now[i]
        for j, (i, v) in enumerate(f_updates.items()):
            flag_slots[k, j] = i
            flag_vals[k, j] = v
        for j, (i, v) in enumerate(r_updates.items()):
            recv_slots[k, j] = i
            recv_vals[k, j] = v
        cand = rng.choice(removable, d_half, replace=False)
        cand = cand[~removed[cand]]
        removed[cand] = True
        mask_rows[k, : cand.size] = slot_ri[cand]
        mask_cols[k, : cand.size] = slot_col[cand]
        set_bits(del_words[k], pdst[cand])

        fresh = []
        while len(fresh) < i_half and n_ins_total + len(fresh) < cap:
            s_, d_ = int(rng.integers(0, n)), int(rng.integers(0, n))
            key = (s_ << 32) | d_
            if key in new_keys:
                continue
            pos = np.searchsorted(base_sorted, key)
            if pos < base_sorted.size and base_sorted[pos] == key:
                continue
            new_keys.add(key)
            fresh.append((s_, d_))
        ins_pairs.extend(fresh)
        n_ins_total = len(ins_pairs)
        # tier snapshot at wake k = every insert so far
        xsrc[k, :n_ins_total] = [p[0] for p in ins_pairs]
        xdst[k, :n_ins_total] = [p[1] for p in ins_pairs]
        set_bits(fresh_words[k], [p[1] for p in fresh])

        if use_jump:
            # Stage this wake's jump-parent maintenance (the
            # IncrementalPallasLayout rules): a removal invalidates the
            # pointer built from it, an insert folds in by minimum.
            aff = []
            rd, rs = pdst[cand], psrc[cand]
            hit = jp_now[rd] == rs
            jp_now[rd[hit]] = n
            aff.append(rd[hit])
            if fresh:
                fs = np.array([p[0] for p in fresh], np.int32)
                fd = np.array([p[1] for p in fresh], np.int64)
                prev = jp_now[fd].copy()
                np.minimum.at(jp_now, fd, fs)
                aff.append(fd[jp_now[fd] != prev])
            aff = np.unique(np.concatenate(aff))
            jw_idx[k, : aff.size] = aff
            jw_val[k, : aff.size] = jp_now[aff]

    dev = {
        "bmeta1": jax.device_put(prep["bmeta1"]),
        "bmeta2": jax.device_put(prep["bmeta2"]),
        "row_pos": jax.device_put(prep["row_pos"]),
        "emeta": jax.device_put(prep["emeta"]),
        "mask_rows": jax.device_put(mask_rows),
        "mask_cols": jax.device_put(mask_cols),
        "del_w": jax.device_put(del_words.view(np.int32)),
        "fresh_w": jax.device_put(fresh_words.view(np.int32)),
        "xsrc": jax.device_put(xsrc),
        "xdst": jax.device_put(xdst),
        "flags": jax.device_put(flags0),
        "recv": jax.device_put(recv0),
        "flag_slots": jax.device_put(flag_slots),
        "flag_vals": jax.device_put(flag_vals),
        "recv_slots": jax.device_put(recv_slots),
        "recv_vals": jax.device_put(recv_vals),
        "jp0": jax.device_put(jp0),
        "jw_idx": jax.device_put(jw_idx),
        "jw_val": jax.device_put(jw_val),
    }
    zeros_w = jnp.zeros((r_rows, pt.LANE), jnp.int32)

    @jax.jit
    def chained(k_hi, row_pos, emeta):
        # no previous fixpoint: five zero word tables and zero walks
        state0 = (zeros_w,) * 5 + (jnp.zeros((), jnp.int32),)

        def body(k, carry):
            flags, recv, row_pos, emeta, jp, state = carry
            # in-chain churn: node-feature scatters + layout slot masks
            flags = flags.at[dev["flag_slots"][k]].set(
                dev["flag_vals"][k], mode="drop"
            )
            recv = recv.at[dev["recv_slots"][k]].set(
                dev["recv_vals"][k], mode="drop"
            )
            rows = dev["mask_rows"][k]
            cols = dev["mask_cols"][k]
            row_pos = row_pos.at[rows, cols].set(pt._PAD_ROW, mode="drop")
            emeta = emeta.at[rows, cols].set(0, mode="drop")
            if use_jump:
                # jump-parent maintenance lands BEFORE the wake, exactly
                # like the production _sync paths
                jp = jp.at[dev["jw_idx"][k]].set(
                    dev["jw_val"][k], mode="drop"
                )
                jarg = (jp,)
            else:
                jarg = ()
            *state, _stats = wake_raw(
                flags,
                recv,
                dev["del_w"][k],
                dev["fresh_w"][k],
                *state,
                *jarg,
                dev["bmeta1"],
                dev["bmeta2"],
                row_pos,
                emeta,
                dev["xsrc"][k],
                dev["xdst"][k],
            )
            return (flags, recv, row_pos, emeta, jp, tuple(state))

        flags, recv, row_pos, emeta, _jp, state = jax.lax.fori_loop(
            0, k_hi, body,
            (dev["flags"], dev["recv"], row_pos, emeta, dev["jp0"], state0),
        )
        # data dependency on the final marks
        return jnp.sum(state[0]), state

    def run(k_hi):
        t0 = time.perf_counter()
        acc, state = chained(k_hi, dev["row_pos"], dev["emeta"])
        int(acc)  # readback sync
        return time.perf_counter() - t0, state

    log = lambda m: print(m, file=sys.stderr, flush=True)
    log(f"pack {pack_s:.1f}s; compiling chain (mode={mode})...")
    run(2)  # compile + warmup
    ts = []
    for _ in range(3):
        t_short, _ = run(2)
        t_long, state = run(K)
        ts.append((t_long - t_short) / (K - 2))
    per_wake_ms = statistics.median(ts) * 1e3

    result = {
        "bench": "wake_chain",
        "n_actors": n,
        "n_pairs": int(prep["n_pairs"]),
        "wakes_chained": K,
        "churn_per_wake": churn,
        "platform": platform,
        "trace_mode": mode,
        "host_pack_s": round(pack_s, 2),
        "device_per_wake_ms": round(per_wake_ms, 3),
        "target_p50_ms": 10.0,
        "vs_target": round(10.0 / max(per_wake_ms, 1e-9), 4),
    }

    if not args.no_stats:
        # Per-wake sweep counts: the same staged wakes replayed
        # UNCHAINED through the jitted wake fn, which counts its own
        # sweeps (device results feed forward, churn applied host-side
        # from the staged arrays), so the sweep-count reduction is
        # visible next to the chain figure.
        log("sweep-count replay...")
        wake_stats = pdec.get_wake_fn(
            n, specs, prep["n_super"], r_rows, prep["s_rows"], mode=mode,
        )
        flags_k = flags0.copy()
        recv_k = recv0.copy()
        row_pos_h = prep["row_pos"].copy()
        emeta_h = prep["emeta"].copy()
        jp_h = jp0.copy()
        z = np.zeros((r_rows, pt.LANE), np.int32)
        state_r = tuple(jax.device_put(z) for _ in range(5)) + (np.int32(0),)
        sweep_counts = []
        for k in range(K):
            fs, ok = flag_slots[k], flag_slots[k] < n
            flags_k[fs[ok]] = flag_vals[k][ok]
            rs, ok = recv_slots[k], recv_slots[k] < n
            recv_k[rs[ok]] = recv_vals[k][ok]
            mr, ok = mask_rows[k], mask_rows[k] < row_pos_h.shape[0]
            row_pos_h[mr[ok], mask_cols[k][ok]] = pt._PAD_ROW
            emeta_h[mr[ok], mask_cols[k][ok]] = 0
            if use_jump:
                jw, ok = jw_idx[k], jw_idx[k] <= n
                jp_h[jw[ok]] = jw_val[k][ok]
                jarg = (jp_h,)
            else:
                jarg = ()
            out = wake_stats(
                flags_k, recv_k,
                del_words[k].view(np.int32), fresh_words[k].view(np.int32),
                *state_r, *jarg,
                prep["bmeta1"], prep["bmeta2"], row_pos_h, emeta_h,
                xsrc[k], xdst[k],
            )
            *state_r, stats_k = out
            sweep_counts.append(int(stats_k["n_sweeps"]))
        result["sweep_counts"] = sweep_counts
        mean_sweeps = statistics.mean(sweep_counts)
        result["sweeps_mean"] = round(mean_sweeps, 2)
        result["sweeps_max"] = max(sweep_counts)
        result["device_per_sweep_ms"] = round(
            per_wake_ms / max(mean_sweeps, 1e-9), 3
        )

    if not args.no_oracle:
        # oracle on the final state: unpack marks from the chained state
        mark_w = np.asarray(state[0])
        shifts = np.arange(32, dtype=np.int64)
        bits = (mark_w.reshape(-1).astype(np.int64)[:, None] >> shifts) & 1
        got = bits.reshape(-1)[:n] > 0
        live = ~removed
        allsrc = np.concatenate([psrc[live], np.array([p[0] for p in ins_pairs], np.int64)])
        alldst = np.concatenate([pdst[live], np.array([p[1] for p in ins_pairs], np.int64)])
        expected = trace_ops.trace_marks_np(
            flags_now, recv_now, np.full(n, -1, np.int32),
            allsrc, alldst, np.ones(allsrc.size, np.int64),
        )
        result["oracle_ok"] = bool(np.array_equal(got, expected))

    print(json.dumps(result))
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(result, fh, indent=2, sort_keys=True)
    if not args.no_oracle and not result["oracle_ok"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
