"""Training launcher — the end-to-end driver for the HSGD federation.

Two modes:
  * e-health simulation (paper reproduction): --model paper-cnn|paper-lstm
    with --dataset organamnist|mimic3|esr, runs Algorithm 1 on the 3-tier
    partitioned synthetic data and reports the paper's metrics.
  * LLM-scale federation: --arch <assigned arch> (reduced via --smoke) runs
    the compiled HSGD rounds (hospital/device towers + combined backbone,
    exchange every Q, pod-group agg every P) on resampled synthetic token
    streams. ``--adaptive`` closes the §VI loop on this path too: the
    controller re-picks P = Q and η every round from the LLM step's own
    gradient probes, and the byte governor ratchets the compression ladder
    until --byte-budget-mb is honored. --pods simulates G pod groups.

Examples:
  PYTHONPATH=src python -m repro.launch.train --model paper-cnn --rounds 50
  PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b --smoke --steps 10
  PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b --smoke \
      --adaptive --steps 16 --byte-budget-mb 8 --max-interval 8
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import save_checkpoint
from repro.common.backend import enable_compile_cache
from repro.common.config import FederationConfig, TrainConfig, get_config
from repro.core import metrics as MET
from repro.core.baselines import make_runner, merge_groups_for_tdcd
from repro.core.controller import (
    AdaptiveConfig,
    AdaptiveHSGDRunner,
    epsilon_of,
    gaussian_rho,
    ladder_from,
)
from repro.core.hsgd import global_model, init_state, make_group_weights
from repro.data.partition import hybrid_partition
from repro.data.synthetic import DATASETS, flatten_for_tower, make_dataset, vertical_split
from repro.models.split_model import cnn_hybrid, llm_hybrid, lstm_hybrid


def make_paper_model(name: str, dataset: str):
    if name == "paper-cnn":
        return cnn_hybrid(h_rows=11, n_classes=DATASETS[dataset].n_classes)
    spec = DATASETS[dataset]
    if spec.name == "esr":
        return lstm_hybrid(n_features=178, hospital_features=89, n_classes=spec.n_classes)
    return lstm_hybrid(n_features=76, hospital_features=36, n_classes=spec.n_classes)


def ehealth_setup(args):
    """(spec, fed, train, model, X, y, data) of an e-health run: the
    federation, the paper model and the 3-tier partitioned data, placed on
    the default device. ``data`` is the stacked [M, ...] federated set."""
    spec = DATASETS[args.dataset]
    fed = FederationConfig(
        num_groups=args.groups,
        devices_per_group=args.devices,
        alpha=args.alpha,
        local_interval=args.q,
        global_interval=args.p,
        robust_agg=args.robust_agg,
        trim_frac=args.trim_frac,
    )
    train = TrainConfig(
        learning_rate=args.lr,
        lr_halve_every=args.lr_halve_every,
        compression_k=args.compression_k,
        quantization_bits=args.quantization,
    )
    model = make_paper_model(args.model, args.dataset)
    X, y = make_dataset(spec, args.samples, seed=args.seed)
    fdata = hybrid_partition(spec, X, y, fed, seed=args.seed)
    raw = fdata.stacked()
    algo = args.algorithm
    if algo in ("tdcd", "c-tdcd"):
        raw = merge_groups_for_tdcd(raw)
    data = {k: jnp.asarray(v) for k, v in raw.items()}
    return spec, fed, train, model, X, y, data


def run_ehealth(args) -> dict:
    spec, fed, train, model, X, y, data = ehealth_setup(args)
    algo = args.algorithm
    w = make_group_weights(data)

    dp = args.dp_clip > 0.0 and args.dp_sigma > 0.0
    private = dp or args.dp_clip > 0.0 or args.secure_agg
    if private and algo not in ("hsgd", "c-hsgd"):
        raise SystemExit(
            f"--dp-clip/--dp-sigma/--secure-agg drive the HSGD exchange; "
            f"got --algorithm {algo}")

    if args.population:
        if algo != "hsgd":
            raise SystemExit(
                f"--population drives the HSGD cohort loop; got --algorithm {algo}")
        if private:
            raise SystemExit(
                "--population does not combine with the privacy flags yet; "
                "use the fixed-interval or --adaptive e-health path")
        return _run_population_cli(args, model, fed, train, data)

    runner, eff_fed = make_runner(algo, model, fed, train)
    key = jax.random.PRNGKey(args.seed)
    if algo == "jfl":
        state = runner.init(key)
    else:
        state = init_state(key, model, eff_fed, data)

    history = None
    t0 = time.time()
    if args.adaptive:
        if algo not in ("hsgd", "c-hsgd"):
            raise SystemExit(f"--adaptive drives the HSGD loop; got --algorithm {algo}")
        eff_train = runner.train  # c-hsgd defaults (k=0.25, b=128) applied
        acfg = AdaptiveConfig(
            total_steps=args.rounds * fed.global_interval,
            target_bound=args.target_bound,
            byte_budget=args.byte_budget_mb * 1e6,
            max_interval=args.max_interval,
            eta_max=max(args.lr * 10, 0.05),
            # explicit --compression-k/--quantization (or c-hsgd defaults)
            # become the governor's rung 0 — never silently loosened
            ladder=ladder_from(eff_train.compression_k, eff_train.quantization_bits),
            privacy_budget=args.epsilon,
            privacy_delta=args.delta,
            dp_clip=args.dp_clip,
            dp_sigma=args.dp_sigma,
            secure_agg=args.secure_agg,
        )
        controller = AdaptiveHSGDRunner(model, fed, eff_train, acfg)
        state, losses, history = controller.run(
            state, data, w, probe_key=jax.random.PRNGKey(args.seed + 1))
        runner = controller.runner  # executor-cache accounting reads this
        for h in history:
            eps = (f" σ={h['dp_sigma']:.3g} ε={h['epsilon_total']:.3g}"
                   if h.get("dp_sigma") else "")
            print(f"[adaptive] round {h['round']:3d}: P=Q={h['P']:3d} "
                  f"eta={h['eta']:.4g} rung={h['rung']} Γ={h['gamma']:.3g} "
                  f"bytes={h['bytes_total'] / 1e6:.2f}MB "
                  f"loss={h['loss_last']:.4f}{eps}")
    elif private:
        state, losses = runner.run_private(
            state, data, w, rounds=args.rounds, seed=args.seed,
            dp_clip=args.dp_clip, dp_sigma=args.dp_sigma,
            secure_agg=args.secure_agg)
    else:
        state, losses = runner.run(state, data, w, rounds=args.rounds)
    dt = time.time() - t0
    gm = runner.global_model(state, w) if algo == "jfl" else global_model(state, w)

    X1, X2 = vertical_split(spec, X)
    m = MET.evaluate_global(
        model, gm, flatten_for_tower(spec, X1), flatten_for_tower(spec, X2), y
    )
    m["train_loss_first"] = float(losses[0]) if len(losses) else float("nan")
    m["train_loss_final"] = float(losses[-1]) if len(losses) else float("nan")
    m["steps"] = int(len(losses))
    m["wall_s"] = round(dt, 2)
    if history is not None:
        m["adaptive_rounds"] = len(history)
        m["adaptive_bytes_total"] = history[-1]["bytes_total"]
        m["adaptive_final_PQ"] = history[-1]["P"]
        if dp and history:
            m["epsilon"] = history[-1]["epsilon_total"]
            m["delta"] = args.delta
    elif dp:
        # fixed-interval ledger: one Gaussian release per exchange, Λ = P/Q
        # exchanges per round (zCDP composition, same math as the controller)
        releases = args.rounds * eff_fed.lam
        m["epsilon"] = epsilon_of(releases * gaussian_rho(args.dp_sigma),
                                  args.delta)
        m["delta"] = args.delta
    if private:
        m["secure_agg"] = bool(args.secure_agg)
        m["executors_compiled"] = len(runner._round_cache)
    print(json.dumps(m, indent=1))
    if args.checkpoint:
        save_checkpoint(args.checkpoint, gm, step=len(losses), extra={"metrics": m})
        print(f"checkpoint -> {args.checkpoint}")
    return m


def _fault_plan_of(args):
    """The CLI's FaultPlan, or None when every fault knob is at its default
    (fault-free runs stay on the plain population executors)."""
    from repro.core.faults import FaultPlan

    plan = FaultPlan(
        seed=args.fault_seed if args.fault_seed is not None else args.seed,
        dropout_rate=args.fault_dropout,
        nan_rate=args.fault_nan,
        outlier_rate=args.fault_outlier,
        msg_corrupt_rate=args.fault_msg_corrupt,
        msg_loss_rate=args.fault_msg_loss,
        msg_dup_rate=args.fault_msg_dup,
        latency_spike_rate=args.fault_latency,
        preempt_round=args.preempt_round,
    )
    return None if plan.empty else plan


def _run_population_cli(args, model, fed, train, data) -> dict:
    """Population-scale cohort run (ROADMAP item 1): simulated device fleet,
    per-round cohort sampling, sync / semi-async / adaptive wall-clock modes.
    Any fault/checkpoint/resume flag routes to the resilient runtime."""
    from repro.core.population import (
        PopulationConfig,
        run_population,
        run_population_adaptive,
        run_population_resilient,
    )

    pop = PopulationConfig(
        seed=args.trace_seed if args.trace_seed is not None else args.seed,
        devices_per_group=args.pop_devices,
        target_cohort=args.cohort,
        deadline_quantile=args.deadline_quantile,
        staleness_damping=args.staleness_damping,
        max_staleness=args.max_staleness,
        min_quorum=args.min_quorum,
        max_retries=args.max_retries,
        backoff_factor=args.backoff_factor,
    )
    plan = _fault_plan_of(args)
    resilient = plan is not None or args.ckpt_every > 0 or args.resume
    t0 = time.time()
    if resilient:
        if args.population == "adaptive":
            raise SystemExit(
                "--population adaptive does not combine with fault injection /"
                " checkpoint-resume; use sync or semi_async")
        res = run_population_resilient(
            model, fed, train, data, pop, rounds=args.rounds,
            faults=plan, mode=args.population, robust=not args.no_defense,
            t_compute=args.t_compute, ckpt_dir=args.checkpoint,
            ckpt_every=args.ckpt_every, resume=args.resume,
        )
        fl = res["fault_log"]
        out = {
            "mode": args.population,
            "trace_seed": pop.seed,
            "steps": int(len(res["losses"])),
            "loss_first": float(res["losses"][0]),
            "loss_last": float(res["losses"][-1]),
            "sim_seconds": res["sim_seconds"],
            "recovered": res["recovered"],
            "rollbacks": res["rollbacks"],
            "devices_dropped": int(sum(r["dropped"] for r in fl)),
            "grad_faults": int(sum(r["grad_faulted"] for r in fl)),
            "msg_faults": int(sum(r["msg_faulted"] for r in fl)),
            "updates_flagged": float(sum(r["flagged_updates"] for r in fl)),
            "round_retries": int(sum(r["retries"] for r in fl)),
            "executors_compiled": len(res["runner"]._round_cache),
            "wall_s": round(time.time() - t0, 2),
        }
        print(json.dumps(out, indent=1))
        if args.fault_trace:
            res["injector"].save_trace(args.fault_trace)
            print(f"fault trace -> {args.fault_trace}")
        if args.checkpoint and args.ckpt_every == 0:
            # no periodic cadence: persist the final state the classic way
            save_checkpoint(args.checkpoint, res["state"],
                            step=len(res["losses"]),
                            extra={"sim_seconds": res["sim_seconds"]})
            print(f"checkpoint -> {args.checkpoint}")
        return out
    if args.population == "adaptive":
        acfg = AdaptiveConfig(
            total_steps=args.rounds * fed.global_interval,
            target_bound=args.target_bound,
            byte_budget=args.byte_budget_mb * 1e6,
            time_budget=args.time_budget,
            max_interval=args.max_interval,
            eta_max=max(args.lr * 10, 0.05),
            ladder=ladder_from(args.compression_k, args.quantization),
            init_probe=False,
        )
        res = run_population_adaptive(model, fed, train, data, pop, acfg,
                                      t_compute=args.t_compute)
    else:
        res = run_population(model, fed, train, data, pop, rounds=args.rounds,
                             mode=args.population, t_compute=args.t_compute)
    out = {
        "mode": args.population,
        "trace_seed": pop.seed,
        "steps": int(len(res["losses"])),
        "loss_first": float(res["losses"][0]),
        "loss_last": float(res["losses"][-1]),
        "sim_seconds": res["sim_seconds"],
        "staleness_hist": {str(k): v for k, v in res["staleness_hist"].items()},
        "executors_compiled": len(res["runner"]._round_cache),
        "wall_s": round(time.time() - t0, 2),
    }
    print(json.dumps(out, indent=1))
    if args.checkpoint:
        save_checkpoint(args.checkpoint, res["state"], step=len(res["losses"]),
                        extra={"sim_seconds": res["sim_seconds"]})
        print(f"checkpoint -> {args.checkpoint}")
    return out


def run_llm(args) -> dict:
    """LLM-scale federation on synthetic token streams (compiled rounds).

    The previous hand loop had two bugs this runner retires: the exchange ran
    TWICE at step 0 (once before the loop and again at t % q == 0 with t = 0),
    and the whole run trained on one frozen batch — now every exchange
    interval resamples a fresh stream, inside one donating jitted executor
    per (P, Q, k, b) bucket.
    """
    from repro.core.controller import AdaptiveConfig, ladder_from
    from repro.data.synthetic import llm_batch_fn
    from repro.launch.steps import (
        AdaptiveLLMRunner,
        LLMRoundRunner,
        global_llm_params,
        init_llm_params,
    )

    cfg = get_config(args.arch, smoke=args.smoke)
    model = llm_hybrid(cfg, n_tower=1, remat=False)
    params = init_llm_params(jax.random.PRNGKey(args.seed), model, n_pods=args.pods)
    batch_fn = llm_batch_fn(cfg, args.batch, args.seq, n_pods=args.pods,
                            seed=args.seed)

    t0 = time.time()
    history = None
    if args.adaptive:
        acfg = AdaptiveConfig(
            total_steps=args.steps,
            target_bound=args.target_bound,
            byte_budget=args.byte_budget_mb * 1e6,
            max_interval=args.max_interval,
            eta_max=max(args.lr * 10, 0.05),
            ladder=ladder_from(args.compression_k, args.quantization),
        )
        runner = AdaptiveLLMRunner(model, acfg, n_pods=args.pods,
                                   learning_rate=args.lr)
        params, losses, history = runner.run(params, batch_fn)
        for h in history:
            print(f"[adaptive] round {h['round']:3d}: P=Q={h['P']:3d} "
                  f"eta={h['eta']:.4g} rung={h['rung']} Γ={h['gamma']:.3g} "
                  f"bytes={h['bytes_total'] / 1e6:.2f}MB loss={h['loss_last']:.4f}")
    else:
        steps = max(1, args.steps // args.p) * args.p  # whole compiled rounds
        if steps != args.steps:
            print(f"# rounding --steps {args.steps} -> {steps} (whole P={args.p} rounds)")
        runner = LLMRoundRunner(model, n_pods=args.pods)
        params, losses = runner.run_fixed(
            params, batch_fn, steps=steps, P=args.p, Q=args.q, lr=args.lr,
            compression_k=args.compression_k, quant_levels=args.quantization)
        for t in range(0, len(losses), max(1, len(losses) // 10)):
            print(f"step {t:4d} loss {float(losses[t]):.4f}")

    out = {"arch": args.arch, "pods": args.pods,
           "loss_first": float(losses[0]), "loss_last": float(losses[-1]),
           "steps": int(len(losses)), "wall_s": round(time.time() - t0, 2)}
    if history is not None:
        out["adaptive_rounds"] = len(history)
        out["adaptive_bytes_total"] = history[-1]["bytes_total"]
        out["adaptive_final_PQ"] = history[-1]["P"]
    print(json.dumps(out))
    if args.checkpoint:
        # flat {θ0, θ1, θ2} global model (pod mean) — the pre-PR-3 format
        save_checkpoint(args.checkpoint, global_llm_params(params),
                        step=len(losses))
        print(f"checkpoint -> {args.checkpoint}")
    return out


def _validate_args(ap, args):
    """Fail fast, at the CLI boundary, with an argparse error — not deep in
    a dataclass __post_init__ after data generation and model init."""
    for flag in ("fault_dropout", "fault_nan", "fault_outlier",
                 "fault_msg_corrupt", "fault_msg_loss", "fault_msg_dup",
                 "fault_latency"):
        v = getattr(args, flag)
        if not 0.0 <= v <= 1.0:
            ap.error(f"--{flag.replace('_', '-')} must be in [0, 1], got {v}")
    if args.max_retries < 0:
        ap.error(f"--max-retries must be >= 0, got {args.max_retries}")
    if args.backoff_factor <= 1.0:
        ap.error(f"--backoff-factor must be > 1, got {args.backoff_factor}")
    if not 0.0 <= args.min_quorum <= 1.0:
        ap.error(f"--min-quorum must be in [0, 1], got {args.min_quorum}")
    if not 0.0 <= args.trim_frac < 0.5:
        ap.error(f"--trim-frac must be in [0, 0.5), got {args.trim_frac}")
    if args.preempt_round < -1:
        ap.error(f"--preempt-round must be >= 0 (or -1 = never), "
                 f"got {args.preempt_round}")
    if args.ckpt_every < 0:
        ap.error(f"--ckpt-every must be >= 0, got {args.ckpt_every}")
    if (args.resume or args.ckpt_every > 0) and not args.checkpoint:
        ap.error("--resume/--ckpt-every need --checkpoint <dir> to hold the "
                 "checkpoints")
    if args.dp_clip < 0.0:
        ap.error(f"--dp-clip must be >= 0, got {args.dp_clip}")
    if args.dp_sigma < 0.0:
        ap.error(f"--dp-sigma must be >= 0, got {args.dp_sigma}")
    if args.dp_sigma > 0.0 and args.dp_clip <= 0.0:
        ap.error("--dp-sigma > 0 needs --dp-clip > 0 (noise std is σ·C)")
    if not 0.0 < args.delta < 1.0:
        ap.error(f"--delta must be in (0, 1), got {args.delta}")
    if args.epsilon <= 0.0:
        ap.error(f"--epsilon must be > 0, got {args.epsilon}")
    if (args.dp_clip > 0.0 or args.secure_agg) and args.arch:
        ap.error("the privacy flags drive the e-health HSGD path, not --arch")


def parse_args(argv=None):
    """The CLI's validated arguments (``--model`` defaults to paper-cnn
    unless ``--arch`` selects the LLM path)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None, choices=["paper-cnn", "paper-lstm"])
    ap.add_argument("--arch", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--dataset", default="organamnist", choices=list(DATASETS))
    ap.add_argument("--algorithm", default="hsgd",
                    choices=["hsgd", "c-hsgd", "jfl", "tdcd", "c-tdcd", "centralized"])
    ap.add_argument("--groups", type=int, default=10)
    ap.add_argument("--devices", type=int, default=64)
    ap.add_argument("--alpha", type=float, default=0.25)
    ap.add_argument("--samples", type=int, default=2048)
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--p", type=int, default=4)
    ap.add_argument("--q", type=int, default=2)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--lr-halve-every", type=int, default=0)
    ap.add_argument("--compression-k", type=float, default=0.0)
    ap.add_argument("--quantization", type=int, default=0)
    ap.add_argument("--pods", type=int, default=1,
                    help="pod groups G on the LLM path (global agg every P)")
    ap.add_argument("--adaptive", action="store_true",
                    help="closed-loop §VI controller: re-picks P/Q/eta and "
                         "tightens compression online (e-health hsgd/c-hsgd "
                         "and the --arch LLM path)")
    ap.add_argument("--byte-budget-mb", type=float, default=float("inf"),
                    help="modeled comm budget for the whole run, MB (all groups)")
    ap.add_argument("--target-bound", type=float, default=float("inf"),
                    help="Theorem-1 target Ξ the controller keeps Γ(P,Q) under")
    ap.add_argument("--max-interval", type=int, default=32,
                    help="cap on the adaptive P = Q")
    ap.add_argument("--population", default=None,
                    choices=["sync", "semi_async", "adaptive"],
                    help="population-scale cohort run over a simulated device "
                         "fleet: sync (barrier rounds), semi_async (deadline "
                         "quantile + staleness-damped late updates), or "
                         "adaptive (semi_async + the wall-clock governor)")
    ap.add_argument("--pop-devices", type=int, default=64,
                    help="simulated population size per group (registry N)")
    ap.add_argument("--cohort", type=int, default=8,
                    help="devices sampled per group per round")
    ap.add_argument("--deadline-quantile", type=float, default=0.8,
                    help="semi-async round deadline as a duration quantile")
    ap.add_argument("--staleness-damping", type=float, default=0.6,
                    help="late update weight multiplier per round of staleness")
    ap.add_argument("--max-staleness", type=int, default=4,
                    help="updates older than this are dropped, not damped")
    ap.add_argument("--t-compute", type=float, default=0.05,
                    help="nominal per-iteration device compute time (s)")
    ap.add_argument("--time-budget", type=float, default=float("inf"),
                    help="simulated wall-clock budget (s) for the adaptive "
                         "population governor")
    ap.add_argument("--trace-seed", type=int, default=None,
                    help="population trace seed (defaults to --seed)")
    # -- fault-tolerant runtime (population path) ---------------------------
    ap.add_argument("--robust-agg", default="mean",
                    choices=["mean", "median", "trimmed"],
                    help="aggregation over screened device updates when a "
                         "round flags faults (clean rounds always use the "
                         "plain masked mean, bit-identically)")
    ap.add_argument("--trim-frac", type=float, default=0.1,
                    help="per-side trim fraction for --robust-agg trimmed")
    ap.add_argument("--no-defense", action="store_true",
                    help="disable compiled screening + robust aggregation "
                         "(naive executor; faults hit the plain masked mean)")
    ap.add_argument("--fault-dropout", type=float, default=0.0,
                    help="P(device vanishes mid-round)")
    ap.add_argument("--fault-nan", type=float, default=0.0,
                    help="P(device emits NaN gradients in a round)")
    ap.add_argument("--fault-outlier", type=float, default=0.0,
                    help="P(device emits outlier-scaled gradients)")
    ap.add_argument("--fault-msg-corrupt", type=float, default=0.0,
                    help="P(group uplink payload bit-flip corrupted)")
    ap.add_argument("--fault-msg-loss", type=float, default=0.0,
                    help="P(group round update lost)")
    ap.add_argument("--fault-msg-dup", type=float, default=0.0,
                    help="P(group round update duplicated)")
    ap.add_argument("--fault-latency", type=float, default=0.0,
                    help="P(group link stalls for a round)")
    ap.add_argument("--preempt-round", type=int, default=-1,
                    help="coordinator dies at this round (-1 = never); "
                         "resume with --resume from the --checkpoint dir")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="fault schedule seed (defaults to --seed)")
    ap.add_argument("--fault-trace", default=None,
                    help="write the realized fault schedule to this JSON file")
    ap.add_argument("--min-quorum", type=float, default=0.5,
                    help="semi-async: fraction of the cohort that must land "
                         "on time before the deadline stops extending")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="semi-async: deadline re-extensions per round")
    ap.add_argument("--backoff-factor", type=float, default=2.0,
                    help="semi-async: deadline multiplier per retry (> 1)")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint state + ledgers to --checkpoint every N "
                         "rounds (0 = only a final checkpoint)")
    ap.add_argument("--resume", action="store_true",
                    help="resume a --population run from the --checkpoint dir")
    # -- privacy-hardened exchange (e-health hsgd/c-hsgd path) ---------------
    ap.add_argument("--dp-clip", type=float, default=0.0,
                    help="per-row L2 clip C of the fused DP stage (0 = off)")
    ap.add_argument("--dp-sigma", type=float, default=0.0,
                    help="Gaussian noise multiplier σ (noise std = σ·C); "
                         "requires --dp-clip > 0")
    ap.add_argument("--epsilon", type=float, default=float("inf"),
                    help="(ε, δ) privacy budget; with --adaptive the "
                         "controller raises σ / amortizes P and refuses "
                         "rounds that would bust it")
    ap.add_argument("--delta", type=float, default=1e-5,
                    help="δ of the (ε, δ) guarantee")
    ap.add_argument("--secure-agg", action="store_true",
                    help="pairwise-mask the eq. (1) uplink (fixed-point ring; "
                         "single uplinks are uninformative, sums are exact)")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    _validate_args(ap, args)
    if not args.arch and not args.model:
        args.model = "paper-cnn"
    return args


def main(argv=None):
    enable_compile_cache()
    args = parse_args(argv)
    if args.arch:
        return run_llm(args)
    return run_ehealth(args)


if __name__ == "__main__":
    main()
