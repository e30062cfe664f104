"""Command-line interface: train / test / predict.

Reference parity: ``deeplearning4j-cli`` (args4j subcommands
``cli/subcommands/{Train,Test,Predict}.java``).  The reference's
``Train.exec()`` is an empty stub (``Train.java:47-49``); these commands
actually work:

    python -m deeplearning4j_tpu.cli train   --input iris.csv --conf net.json \
        --output model.bin --epochs 50
    python -m deeplearning4j_tpu.cli test    --input iris.csv --model model.bin
    python -m deeplearning4j_tpu.cli predict --input iris.csv --model model.bin \
        --output preds.csv

``--input`` accepts a labeled numeric CSV (label in the last column, the
CSVDataFetcher convention) or the name of a built-in dataset
(``mnist``/``iris``).  ``--conf`` is MultiLayerConfiguration JSON — the
same serialization the config system round-trips.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional

import numpy as np


def _load_dataset(spec: str, batch: int = 0, binarize: bool = True):
    from deeplearning4j_tpu.datasets.fetchers import (
        CSVDataFetcher, IrisDataFetcher, MnistDataFetcher)

    if spec == "iris":
        f = IrisDataFetcher()
        f.fetch(150)
    elif spec in ("mnist", "mnist-test", "mnist2d", "mnist2d-test"):
        # real idx files when $MNIST_DIR (or ./data/mnist) holds them —
        # MnistDataFetcher.java:37 parity — else the synthetic surrogate.
        # "2d" keeps [N, 28, 28, 1] images for conv nets (LeNet); plain
        # "mnist" flattens to [N, 784] for dense nets.  ``binarize``
        # follows the reference default (threshold at 30/255);
        # --raw-pixels turns it off for grayscale conv training.
        f = MnistDataFetcher(train=not spec.endswith("-test"),
                             flatten=not spec.startswith("mnist2d"),
                             binarize=binarize)
        f.fetch(f.total)
    else:
        f = CSVDataFetcher(spec)
        f.fetch(10 ** 9)
    return f.next()


def _load_model(path: str):
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    with open(path, "rb") as fh:
        return MultiLayerNetwork.from_bytes(fh.read())


def cmd_train(args) -> int:
    from deeplearning4j_tpu.nn.conf.configuration import (
        MultiLayerConfiguration)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optimize.listeners import ScoreIterationListener
    from deeplearning4j_tpu.runtime import telemetry

    if not args.checkpoint_dir and (args.resume or args.sync_checkpoints):
        # silently training from scratch here would overwrite --output
        # — exactly the data loss --resume exists to avoid
        raise SystemExit(
            "--resume/--sync-checkpoints require --checkpoint-dir")
    if args.checkpoint_dir and args.checkpoint_every <= 0:
        raise SystemExit("--checkpoint-every must be a positive step "
                         "count")
    # multi-host launcher: merge the flag trio with the DL4J_TPU_* env
    # trio (flags > env, one source of truth: multihost
    # .resolve_cluster_config), join with bounded retry/backoff, and
    # hand the cluster to the ResilientFit driver below
    cluster = None
    from deeplearning4j_tpu.parallel import multihost
    try:
        cluster_cfg = multihost.resolve_cluster_config(
            args.coordinator, args.num_processes, args.process_id)
    except ValueError as e:
        raise SystemExit(str(e))
    if cluster_cfg is not None and cluster_cfg.num_processes > 1:
        if not args.checkpoint_dir:
            raise SystemExit(
                "multi-process training requires --checkpoint-dir: "
                "cluster-committed snapshots (on a filesystem every "
                "host shares) are the substrate preemption and "
                "host-loss recovery coordinate through")
        try:
            cluster = multihost.initialize(cluster_cfg)
        except multihost.ClusterJoinError as e:
            raise SystemExit(f"cluster join failed: {e}")
        print(f"joined cluster: process {cluster.process_id} of "
              f"{cluster.process_count} at {cluster_cfg.coordinator}")
    tracer = None
    journal_dir = args.telemetry
    if journal_dir is True:                 # bare --telemetry flag
        journal_dir = telemetry.DEFAULT_JOURNAL_DIR
    if journal_dir:
        tracer = telemetry.enable()
        telemetry.registry.mark()
    try:
        with open(args.conf) as fh:
            conf = MultiLayerConfiguration.from_json(fh.read())
        data = _load_dataset(args.input,
                             binarize=not args.raw_pixels)
        net = MultiLayerNetwork(conf).init(seed=args.seed)
        net.set_listeners([ScoreIterationListener(args.log_every)])
        batches = (data.batch_by(args.batch) if args.batch > 0 else data)
        if args.checkpoint_dir:
            # preemption-tolerant path: async snapshots + signal guard;
            # SIGTERM mid-fit commits a final snapshot and returns here
            # cleanly (exit 0) — rerun with --resume to continue
            from deeplearning4j_tpu.runtime.resilience import (
                ResilienceConfig, ResilientFit)
            if conf.pretrain:
                raise SystemExit(
                    "--checkpoint-dir drives the backprop trainer; "
                    "pretrain confs must use the plain train path")
            # dir-state misuse fails BEFORE the finetune pass is spent,
            # and as a one-line SystemExit like every sibling guard —
            # not a raw traceback out of ResilientFit
            from deeplearning4j_tpu.runtime.checkpoint import (
                CheckpointManager)
            latest = CheckpointManager(args.checkpoint_dir).latest_step()
            if args.resume and latest is None:
                # empty/mistyped dir (unmounted volume?): silently
                # training from scratch would overwrite --output with a
                # from-step-0 rerun — the data loss --resume exists to
                # avoid
                raise SystemExit(
                    f"--resume: no checkpoints found in "
                    f"{args.checkpoint_dir} — wrong path or unmounted "
                    "volume? rerun without --resume for a fresh run")
            if not args.resume and latest is not None:
                raise SystemExit(
                    f"--checkpoint-dir {args.checkpoint_dir} already "
                    f"holds snapshots (latest step {latest}) — rerun "
                    "with --resume to continue that run, or point at a "
                    "fresh directory")
            # net.fit's own stage prep (finetune pass + gated
            # mesh="auto") so adding --checkpoint-dir never changes
            # WHAT is trained; on a resume the restore overwrites the
            # finetuned params — harmless
            batch_list, mesh = net.prepare_resilient_fit(batches)
            driver = ResilientFit(net, ResilienceConfig(
                checkpoint_dir=args.checkpoint_dir,
                checkpoint_every=args.checkpoint_every,
                resume=args.resume, sync=args.sync_checkpoints),
                mesh=mesh, cluster=cluster)
            driver.fit(batch_list, num_epochs=args.epochs, seed=args.seed)
            if driver.evicted:
                print("host loss: this process's devices were lost — "
                      "exiting cleanly; the surviving hosts carry the "
                      "run (resume from the cluster-committed "
                      f"snapshots in {args.checkpoint_dir})")
                return 0
            if driver.preempted:
                print(f"preempted: final snapshot committed at step "
                      f"{driver.manager.latest_step()} in "
                      f"{args.checkpoint_dir} — rerun with --resume")
                # the grace window is burning: skip the model write and
                # the full-dataset evaluate — the committed snapshot IS
                # this run's output, and a SIGKILL landing mid-write
                # would leave a truncated --output worse than none
                return 0
        else:
            net.fit(batches, num_epochs=args.epochs)
        with open(args.output, "wb") as fh:
            fh.write(net.to_bytes())
        ev = net.evaluate(data)
        print(f"saved model to {args.output}")
        print(f"train accuracy: {ev.accuracy():.4f}")
    finally:
        # export even when the fit raises or is interrupted — a failed
        # run is exactly when the journal is needed for the post-mortem
        if tracer is not None:
            import os
            os.makedirs(journal_dir, exist_ok=True)
            journal = os.path.join(journal_dir, f"{tracer.run_id}.jsonl")
            tracer.export_journal(journal,
                                  snapshot=telemetry.registry.snapshot())
            print(f"telemetry journal: {journal}  (summarize with "
                  f"`python -m deeplearning4j_tpu.cli telemetry "
                  f"--journal {journal}`)")
    return 0


def cmd_test(args) -> int:
    net = _load_model(args.model)
    data = _load_dataset(args.input, binarize=not args.raw_pixels)
    ev = net.evaluate(data)
    print(ev.stats())
    return 0


def cmd_predict(args) -> int:
    net = _load_model(args.model)
    data = _load_dataset(args.input, binarize=not args.raw_pixels)
    preds = np.asarray(net.predict(data.features))
    if args.output:
        np.savetxt(args.output, preds, fmt="%d")
        print(f"wrote {len(preds)} predictions to {args.output}")
    else:
        for p in preds:
            print(int(p))
    return 0


def _gpt_save_npz(path: str, cfg, params, chars: str) -> None:
    """Persist a char-GPT as one .npz: nested param dict flattened to
    slash-joined keys + a JSON header with the config and vocab."""
    import dataclasses

    flat = {}

    def walk(prefix, node):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(f"{prefix}/{k}" if prefix else k, v)
        else:
            flat[prefix] = np.asarray(node)

    walk("", params)
    header = json.dumps({"cfg": dataclasses.asdict(cfg), "chars": chars})
    np.savez(path, __conf__=np.asarray(header), **flat)


def _gpt_load_npz(path: str):
    from deeplearning4j_tpu.models.transformer import TransformerConfig

    data = np.load(path, allow_pickle=False)
    meta = json.loads(str(data["__conf__"]))
    params: dict = {}
    for key in data.files:
        if key == "__conf__":
            continue
        node = params
        parts = key.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = data[key]
    return TransformerConfig(**meta["cfg"]), params, meta["chars"]


def cmd_generate(args) -> int:
    """Continuous-batching text generation (serving/decode.py): serve
    every ``--prompt`` CONCURRENTLY through ``Router`` replicas of
    slot-structured ``DecodeEngine``s — requests join the running decode
    batch mid-flight instead of queueing behind each other.  The model
    is a char-level GPT: either ``--params`` (an .npz saved by a prior
    run's ``--save-params``) or trained on the fly from ``--input``
    text."""
    import time as _time

    import jax

    from deeplearning4j_tpu.models import gpt
    from deeplearning4j_tpu.runtime import telemetry
    from deeplearning4j_tpu.runtime.metrics import decode_metrics
    from deeplearning4j_tpu.serving.router import OverloadedError, Router

    tracer = None
    journal_dir = args.telemetry
    if journal_dir is True:
        journal_dir = telemetry.DEFAULT_JOURNAL_DIR
    if journal_dir:
        tracer = telemetry.enable()

    if args.params:
        cfg, params, chars = _gpt_load_npz(args.params)
        print(f"loaded char-GPT from {args.params} "
              f"(vocab {cfg.vocab_size}, max_len {cfg.max_len})")
    else:
        if args.input:
            with open(args.input) as fh:
                text = fh.read()
        else:
            text = "the quick brown fox jumps over the lazy dog. " * 64
        chars = "".join(sorted(set(text)))
        stoi = {c: i for i, c in enumerate(chars)}
        ids = np.asarray([stoi[c] for c in text], np.int32)
        cfg = gpt.gpt_tiny(vocab_size=len(chars), max_len=args.max_len)
        from deeplearning4j_tpu.parallel.mesh import MeshSpec, make_mesh
        init_fn, step_fn = gpt.make_train_step(cfg, make_mesh(MeshSpec()))
        state = init_fn(jax.random.key(args.seed))
        T = min(32, cfg.max_len)
        ndev = len(jax.devices())
        reps = -(-(T * ndev + 1) // ids.size)
        if reps > 1:
            ids = np.tile(ids, reps)
        n = max((ids.size - 1) // T // ndev, 1) * ndev
        x = ids[:n * T].reshape(n, T)
        key = jax.random.key(1)
        print(f"training char-GPT ({args.train_steps} steps, vocab "
              f"{len(chars)}) ...")
        loss = None
        for _ in range(args.train_steps):
            state, loss = step_fn(state, x, key)
        if loss is not None:
            print(f"final LM loss: {float(loss):.3f}")
        params = jax.tree.map(np.asarray, state.params)
        if args.save_params:
            _gpt_save_npz(args.save_params, cfg, params, chars)
            print(f"saved params to {args.save_params}")

    stoi = {c: i for i, c in enumerate(chars)}
    prompts = args.prompt or ["the quick "]
    enc = [np.asarray([stoi.get(c, 0) for c in p], np.int32)
           for p in prompts]

    telemetry.registry.mark()
    router = Router.replicate(
        cfg, params, args.replicas, n_slots=args.slots,
        max_queue_depth=args.max_queue_depth,
        default_max_tokens=args.max_tokens)
    t0 = _time.perf_counter()
    with router:
        handles = []
        for p, e in zip(prompts, enc):
            try:
                handles.append((p, router.submit(
                    e, max_tokens=args.max_tokens,
                    temperature=args.temperature, seed=args.seed)))
            except OverloadedError as err:
                print(f"SHED  {p!r}: {err}")
        for p, h in handles:
            toks = h.result(args.timeout)
            text_out = "".join(chars[t] if t < len(chars) else "?"
                               for t in toks)
            print(f"{p!r} -> {p + text_out!r}")
    wall = _time.perf_counter() - t0
    snap = decode_metrics.snapshot()
    print(f"\n{snap['tokens_out']} tokens in {wall:.2f}s "
          f"({snap['tokens_out'] / max(wall, 1e-9):.1f} tok/s) | "
          f"ttft p50/p99 {snap['ttft_p50_ms']}/{snap['ttft_p99_ms']} ms | "
          f"slot occupancy {snap['slot_occupancy']:.2f} | "
          f"joins {snap['joins']} | compile_delta "
          f"{snap.get('compile_delta_since_mark')}")
    if tracer is not None:
        import os
        os.makedirs(journal_dir, exist_ok=True)
        journal = os.path.join(journal_dir, f"{tracer.run_id}.jsonl")
        tracer.export_journal(journal,
                              snapshot=telemetry.registry.snapshot())
        print(f"telemetry journal: {journal}")
    return 0


def cmd_telemetry(args) -> int:
    """Summarize a telemetry journal (runtime/telemetry.py JSONL): span
    tree with aggregate timings, top-k longest spans, event counts, and
    counter deltas between the journal's first and last registry
    snapshots.  ``--export-trace`` additionally converts the journal to
    chrome://tracing/Perfetto trace JSON."""
    from deeplearning4j_tpu.runtime import telemetry

    records = telemetry.read_journal(args.journal)
    summary = telemetry.summarize_journal(records, top_k=args.top)

    if args.json:
        print(json.dumps(summary, indent=2, default=str))
    else:
        for run in summary["runs"]:
            dropped = run.get("dropped", 0)
            print(f"run {run.get('run_id')}  (dropped records: {dropped})")
        print(f"{summary['n_spans']} span(s), "
              f"{summary['n_events']} event(s)")
        if summary["tree"]:
            print("\nspan tree (aggregated by name under parent):")
            print(f"  {'span':<44} {'count':>6} {'total ms':>10} "
                  f"{'mean ms':>9} {'max ms':>9}")
            for row in summary["tree"]:
                label = "  " * row["depth"] + row["name"]
                print(f"  {label:<44} {row['count']:>6} "
                      f"{row['total_ms']:>10.2f} {row['mean_ms']:>9.2f} "
                      f"{row['max_ms']:>9.2f}")
        if summary["top"]:
            print(f"\ntop {len(summary['top'])} spans by duration:")
            for r in summary["top"]:
                print(f"  {r['dur_ms']:>10.2f} ms  {r['name']}"
                      f"  @{r['ts']:.3f}s  {r['attrs'] or ''}")
        if summary["events"]:
            print("\nevents:")
            for name, n in sorted(summary["events"].items()):
                print(f"  {n:>6} x {name}")
        if "counter_deltas" in summary:
            print("\ncounter deltas (last snapshot - first):")
            print(json.dumps(summary["counter_deltas"], indent=2,
                             default=str))
        elif "counters" in summary:
            print("\ncounters (single snapshot):")
            print(json.dumps(summary["counters"], indent=2, default=str))

    if args.export_trace:
        run_id = summary["runs"][0].get("run_id", "run") \
            if summary["runs"] else "run"
        payload = telemetry.chrome_trace(records, run_id=run_id)
        with open(args.export_trace, "w") as fh:
            json.dump(payload, fh)
        print(f"\nwrote Perfetto trace JSON to {args.export_trace} "
              f"({len(payload['traceEvents'])} events) — load at "
              "https://ui.perfetto.dev or chrome://tracing")
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="deeplearning4j_tpu",
        description="TPU-native deeplearning4j: train/test/predict")
    sub = p.add_subparsers(dest="command", required=True)

    t = sub.add_parser("train", help="fit a model from a conf JSON")
    t.add_argument("--input", required=True,
                   help="labeled CSV path, or 'iris'/'mnist[2d][-test]' "
                        "(mnist reads $MNIST_DIR idx files when present)")
    t.add_argument("--conf", required=True,
                   help="MultiLayerConfiguration JSON file")
    t.add_argument("--output", required=True, help="model output path")
    t.add_argument("--epochs", type=int, default=1)
    t.add_argument("--batch", type=int, default=0,
                   help="minibatch size (0 = full batch)")
    t.add_argument("--seed", type=int, default=0)
    t.add_argument("--raw-pixels", action="store_true",
                   help="keep mnist pixels as [0,1] floats instead of the "
                        "reference's >30/255 binarization")
    t.add_argument("--log-every", type=int, default=10)
    # const=True: a bare `--telemetry` resolves to the default journal
    # dir (runtime.telemetry.DEFAULT_JOURNAL_DIR, honoring
    # $DL4J_TPU_TELEMETRY_DIR) at use time — resolved in cmd_train so
    # building the parser never imports the runtime
    t.add_argument("--telemetry", nargs="?", default=None, const=True,
                   metavar="DIR",
                   help="enable the run tracer and write a JSONL journal "
                        "into DIR (bare --telemetry uses the gitignored "
                        "'.dl4j_telemetry', or $DL4J_TPU_TELEMETRY_DIR)")
    t.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                   help="train through the preemption-tolerant "
                        "ResilientFit driver: async background snapshots "
                        "into DIR, SIGTERM/SIGINT triggers a final "
                        "committed snapshot + clean exit 0")
    t.add_argument("--checkpoint-every", type=int, default=50,
                   metavar="STEPS", help="snapshot cadence in steps")
    t.add_argument("--resume", action="store_true",
                   help="continue from the newest committed checkpoint "
                        "in --checkpoint-dir (the restart half of the "
                        "preemption drill)")
    t.add_argument("--sync-checkpoints", action="store_true",
                   help="escape hatch: block the training thread on "
                        "every snapshot instead of the async writer")
    # multi-host launcher trio (parallel/multihost.py owns the
    # contract): flags override the DL4J_TPU_COORDINATOR/
    # NUM_PROCESSES/PROCESS_ID env trio per field; every host runs the
    # SAME command with its own --process-id (or the provision
    # scripts' exported env) and the processes form one
    # jax.distributed cluster with a global device mesh
    t.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="jax.distributed coordinator address "
                        "(env: DL4J_TPU_COORDINATOR); set the trio "
                        "to train across processes/hosts")
    t.add_argument("--num-processes", type=int, default=None,
                   metavar="N",
                   help="total processes in the cluster "
                        "(env: DL4J_TPU_NUM_PROCESSES)")
    t.add_argument("--process-id", type=int, default=None,
                   metavar="I",
                   help="this process's rank in [0, N) "
                        "(env: DL4J_TPU_PROCESS_ID)")
    t.set_defaults(fn=cmd_train)

    e = sub.add_parser("test", help="evaluate a saved model")
    e.add_argument("--input", required=True)
    e.add_argument("--model", required=True)
    e.add_argument("--raw-pixels", action="store_true")
    e.set_defaults(fn=cmd_test)

    r = sub.add_parser("predict", help="class predictions for a dataset")
    r.add_argument("--input", required=True)
    r.add_argument("--model", required=True)
    r.add_argument("--output", default=None)
    r.add_argument("--raw-pixels", action="store_true")
    r.set_defaults(fn=cmd_predict)

    g = sub.add_parser(
        "generate",
        help="continuous-batching char-GPT text generation "
             "(serving/decode.py): all --prompt requests decode "
             "concurrently in one slot-structured batch")
    g.add_argument("--input", default=None,
                   help="text file to build the char vocab from and "
                        "train on (default: a built-in demo phrase)")
    g.add_argument("--params", default=None, metavar="NPZ",
                   help="load a char-GPT saved by --save-params instead "
                        "of training")
    g.add_argument("--save-params", default=None, metavar="NPZ",
                   help="save the freshly trained char-GPT for reuse")
    g.add_argument("--prompt", action="append", default=None,
                   help="prompt text (repeatable; each one is a "
                        "concurrent request)")
    g.add_argument("--max-tokens", type=int, default=48)
    g.add_argument("--temperature", type=float, default=0.3,
                   help="0 = greedy argmax")
    g.add_argument("--seed", type=int, default=0)
    g.add_argument("--max-len", type=int, default=128,
                   help="model context length (prompt + continuation "
                        "must fit)")
    g.add_argument("--train-steps", type=int, default=300)
    g.add_argument("--replicas", type=int, default=1,
                   help="decode engine replicas behind the router")
    g.add_argument("--slots", type=int, default=8,
                   help="concurrent sequences per engine")
    g.add_argument("--max-queue-depth", type=int, default=64,
                   help="router load-shed bound (OverloadedError above)")
    g.add_argument("--timeout", type=float, default=300.0)
    g.add_argument("--telemetry", nargs="?", default=None, const=True,
                   metavar="DIR",
                   help="enable the run tracer and write a JSONL journal")
    g.set_defaults(fn=cmd_generate)

    m = sub.add_parser(
        "telemetry",
        help="summarize a run-telemetry journal (span tree, top-k "
             "durations, counter deltas; optional Perfetto export)")
    m.add_argument("--journal", required=True,
                   help="JSONL journal written by "
                        "runtime/telemetry.py export_journal()")
    m.add_argument("--top", type=int, default=10,
                   help="how many longest spans to list")
    m.add_argument("--json", action="store_true",
                   help="emit the summary as JSON instead of text")
    m.add_argument("--export-trace", default=None, metavar="PATH",
                   help="also convert the journal to chrome://tracing/"
                        "Perfetto trace JSON at PATH")
    m.set_defaults(fn=cmd_telemetry)
    return p


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    from deeplearning4j_tpu.runtime import ensure_compile_cache

    ensure_compile_cache()
    sys.exit(main())
