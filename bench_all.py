"""BASELINE configs 2-5 measured through the public APIs.

``bench.py`` is the judged harness (config 1 MLP + the MFU-accounted LM);
this script measures the remaining BASELINE.md target configs:

- **2** MNIST-CNN through ``SparkModel`` in synchronous AND async/hogwild
  modes — throughput plus the convergence envelope (same model/data/epochs,
  final test accuracy per mode: async staleness trades accuracy for
  pipeline overlap; the envelope quantifies it).
- **3** IMDB-LSTM through the ``ElephasEstimator`` Spark-ML pipeline.
- **4** ``SparkMLlibModel`` on LabeledPoint RDDs (Boston-shaped regression
  + Iris multiclass).
- **5** ``HyperParamModel`` distributed search wall-clock.

Prints ONE JSON line ``{"configs": {...}}`` (stderr carries progress).
Config 2 reports steady-state throughput (a warmup fit absorbs compile);
configs 3-5 are one-shot API flows, so their wall-clock INCLUDES compile —
stated in the output rather than hidden.

Datasets are the examples' offline synthetic fallbacks (``examples/_datasets``)
— identical shapes/dtypes to the real ones, no network. Knobs:
``BENCH_ALL_SAMPLES``, ``BENCH_ALL_EPOCHS``, ``BENCH_ALL_EVALS``.
"""

import json
import os
import sys
import time
import traceback

os.environ.setdefault("KERAS_BACKEND", "jax")
_REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_REPO, "examples"))


def log(*args):
    print(*args, file=sys.stderr, flush=True)


def _accuracy(model_like, x, y):
    import numpy as np

    preds = np.asarray(model_like.predict(x))
    return float((preds.argmax(1) == y.argmax(1)).mean())


def config2_mnist_cnn():
    """Sync vs async vs hogwild CNN: samples/sec/chip + accuracy envelope.

    Async/hogwild each measure BOTH schedules: ``compiled``
    (``parameter_server_mode='jax'`` — the TPU-first path, whole run in one
    XLA program with documented one-period staleness) and ``host`` (live
    parameter server through HTTP, the reference's semantics). The envelope
    is only meaningful off the accuracy ceiling, so the default geometry is
    ONE epoch (BENCH_ALL_C2_EPOCHS to override) — at 3 epochs every mode
    used to hit test accuracy 1.000 and the measured envelope was vacuously
    0.000.
    """
    import jax
    import numpy as np

    from elephas_tpu import SparkModel
    from elephas_tpu.data import SparkContext
    from elephas_tpu.utils import to_simple_rdd

    from _datasets import load_mnist
    from mnist_cnn_async import make_cnn

    n = int(os.environ.get("BENCH_ALL_SAMPLES", 8192))
    epochs = int(os.environ.get(
        "BENCH_ALL_C2_EPOCHS", os.environ.get("BENCH_ALL_EPOCHS", 1)))
    n_dev = jax.local_device_count()
    n_workers = max(n_dev, 2)

    (x_tr, y_tr), (x_te, y_te) = load_mnist(n_train=n, n_test=1024)
    sc = SparkContext(master=f"local[{n_workers}]", appName="bench_all_c2")
    rdd = to_simple_rdd(sc, x_tr, y_tr, num_slices=n_workers)

    cells = (
        ("sync", "synchronous", "jax"),
        ("async_compiled", "asynchronous", "jax"),
        ("async_host", "asynchronous", "http"),
        ("hogwild_compiled", "hogwild", "jax"),
        ("hogwild_host", "hogwild", "http"),
    )
    out = {}
    for name, mode, ps_mode in cells:
        sm = SparkModel(make_cnn(), mode=mode, frequency="epoch",
                        num_workers=n_workers, merge="mean",
                        parameter_server_mode=ps_mode)
        sm.fit(rdd, epochs=epochs, batch_size=64, verbose=0,
               validation_split=0.0)  # warmup: compile at this geometry
        acc = _accuracy(sm, x_te, y_te)  # accuracy after the FIRST fit:
        # the envelope compares one pass from identical fresh weights
        t0 = time.perf_counter()
        sm.fit(rdd, epochs=epochs, batch_size=64, verbose=0,
               validation_split=0.0)
        dt = time.perf_counter() - t0
        sps_chip = n * epochs / dt / n_dev
        out[name] = {
            "samples_per_sec_per_chip": round(sps_chip, 1),
            "test_accuracy": round(acc, 4),
        }
        log(f"config2 {name} ({mode}/{ps_mode}): {sps_chip:,.0f} "
            f"samples/sec/chip steady-state, first-fit acc {acc:.4f}")
    sc.stop()
    # convergence envelope: each cell's first-fit accuracy relative to sync
    sync_acc = out["sync"]["test_accuracy"]
    for name in out:
        if name != "sync":
            out[name]["accuracy_vs_sync"] = round(
                out[name]["test_accuracy"] - sync_acc, 4
            )
    return out


def config3_imdb_lstm():
    """ElephasEstimator pipeline on IMDB-shaped data.

    Two figures since round 5 (the config-2/6 marginal discipline applied
    to the L5 skins): the one-shot wall-clock incl. compile (the honest
    DataFrame-API first-use number), and the MARGINAL steady-state rate
    from differencing fits at two epoch counts after per-geometry warmups
    — per-fit fixed cost (compile, DataFrame conversion, weight
    round-trips) cancels, leaving the compiled program's per-step rate.
    """
    import jax
    import numpy as np

    from elephas_tpu import ElephasEstimator
    from elephas_tpu.data import Row, SparkSession
    from elephas_tpu.ml import Pipeline
    from elephas_tpu.mllib import Vectors

    from _datasets import load_imdb
    from ml_pipeline_imdb_lstm import MAXLEN, VOCAB, make_lstm

    n = int(os.environ.get("BENCH_ALL_SAMPLES", 8192)) // 4
    epochs = int(os.environ.get("BENCH_ALL_EPOCHS", 3))
    n_dev = jax.local_device_count()

    spark = SparkSession.builder.master(f"local[{n_dev}]").appName(
        "bench_all_c3").getOrCreate()
    (x_tr, y_tr), (x_te, y_te) = load_imdb(n_train=n, n_test=512,
                                           maxlen=MAXLEN, vocab=VOCAB)
    df = spark.createDataFrame([
        Row(features=Vectors.dense(x.astype("float64")), label=float(y[0]))
        for x, y in zip(x_tr, y_tr)
    ])
    est = ElephasEstimator()
    est.set_keras_model(make_lstm())
    est.set_categorical(False)
    est.set_num_workers(n_dev)
    est.set_epochs(epochs)
    est.set_batch_size(32)  # partitions must exceed the batch (skip quirk)
    est.set_validation_split(0.0)
    est.set_mode("synchronous")
    est.set_parameter_server_mode("jax")

    t0 = time.perf_counter()
    fitted = Pipeline(stages=[est]).fit(df)
    dt = time.perf_counter() - t0

    test_df = spark.createDataFrame([
        Row(features=Vectors.dense(x.astype("float64")), label=float(y[0]))
        for x, y in zip(x_te, y_te)
    ])
    rows = fitted.transform(test_df).collect()
    preds = np.array([r.prediction for r in rows])
    labels = np.array([r.label for r in rows])
    acc = float(((preds > 0.5) == (labels > 0.5)).mean())

    # marginal steady-state: difference estimator fits at two epoch
    # counts (each epoch count is its own compiled program — warm up
    # both geometries first, then best-of-2)
    e_lo, e_hi = 1, 1 + 2 * epochs

    def best_est_fit(n_epochs, reps=2):
        est.set_epochs(n_epochs)
        Pipeline(stages=[est]).fit(df)  # warmup/compile this geometry
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            Pipeline(stages=[est]).fit(df)
            best = min(best, time.perf_counter() - t0)
        return best

    t_lo = best_est_fit(e_lo)
    t_hi = best_est_fit(e_hi)
    # same timer-noise floor _marginal_fit_sps enforces: a differenced
    # wall below resolution must report None, not a fantasy rate
    sps_marginal = (
        n * (e_hi - e_lo) / (t_hi - t_lo)
        if t_hi - t_lo >= _MARGINAL_FLOOR_S else None)
    log(f"config3 imdb-lstm pipeline: {n * epochs / dt:,.0f} samples/sec "
        f"(incl. compile); marginal steady-state "
        + (f"{sps_marginal:,.0f} samples/sec" if sps_marginal
           else "below timer floor")
        + f"; acc {acc:.4f}")
    return {
        "samples_per_sec_incl_compile": round(n * epochs / dt, 1),
        "samples_per_sec_marginal":
            round(sps_marginal, 1) if sps_marginal else None,
        "test_accuracy": round(acc, 4),
    }


_MARGINAL_FLOOR_S = 0.05  # differenced wall below this is timer noise


def _marginal_fit_sps(m, fit_kwargs, n_samples, e_lo, e_hi, reps=2):
    """Round-5 shared helper: marginal steady-state samples/sec from
    differencing fits at two epoch counts (per-geometry warmups; per-fit
    fixed cost cancels). Returns ``None`` when the differenced wall is
    below the timer-noise floor — tiny-dataset fits can complete their
    extra epochs faster than the measurement resolves, and a clamped
    division would report a fantasy number."""
    def best(n_epochs):
        m.fit(epochs=n_epochs, **fit_kwargs)  # warmup/compile
        b = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            m.fit(epochs=n_epochs, **fit_kwargs)
            b = min(b, time.perf_counter() - t0)
        return b

    t_lo, t_hi = best(e_lo), best(e_hi)
    if t_hi - t_lo < _MARGINAL_FLOOR_S:
        return None
    return n_samples * (e_hi - e_lo) / (t_hi - t_lo)


def config4_mllib():
    """SparkMLlibModel: Boston-shaped regression MSE + Iris accuracy —
    one-shot wall incl. compile AND (round 5) the marginal steady-state
    rate via the config-2/6 differencing discipline."""
    import jax
    import keras
    import numpy as np

    from elephas_tpu import SparkMLlibModel
    from elephas_tpu.data import SparkContext
    from elephas_tpu.utils import to_labeled_point

    from _datasets import load_boston, load_iris

    n_dev = jax.local_device_count()
    epochs = int(os.environ.get("BENCH_ALL_EPOCHS", 3)) * 7
    sc = SparkContext(master=f"local[{n_dev}]", appName="bench_all_c4")

    # regression
    x, y = load_boston()
    x = (x - x.mean(0)) / (x.std(0) + 1e-6)
    y_n = (y - y.mean()) / y.std()
    lp = to_labeled_point(sc, x, y_n, categorical=False)
    reg = keras.Sequential(
        [keras.layers.Dense(32, activation="relu"), keras.layers.Dense(1)]
    )
    reg.build((None, x.shape[1]))
    reg.compile(optimizer="adam", loss="mse")
    m = SparkMLlibModel(reg, mode="synchronous", num_workers=n_dev)
    t0 = time.perf_counter()
    m.fit(lp, epochs=epochs, batch_size=32, validation_split=0.0,
          categorical=False)
    dt_reg = time.perf_counter() - t0
    mse = float(np.mean(
        (np.asarray(m.predict(x)).ravel() - y_n) ** 2
    ))

    # multiclass (load_iris yields class ids)
    xi, yi = load_iris()
    xi = (xi - xi.mean(0)) / (xi.std(0) + 1e-6)
    lpi = to_labeled_point(sc, xi, yi, categorical=True)
    clf = keras.Sequential([
        keras.layers.Dense(16, activation="relu"),
        keras.layers.Dense(3, activation="softmax"),
    ])
    clf.build((None, xi.shape[1]))
    clf.compile(optimizer="adam", loss="categorical_crossentropy",
                metrics=["accuracy"])
    mc = SparkMLlibModel(clf, mode="synchronous", num_workers=n_dev)
    t0 = time.perf_counter()
    mc.fit(lpi, epochs=epochs, batch_size=16, validation_split=0.0,
           categorical=True, nb_classes=3)
    dt_cls = time.perf_counter() - t0
    acc = float(
        (np.asarray(mc.predict(xi)).argmax(1) == yi.astype(int)).mean()
    )
    # marginal steady-state for both skins (fixed per-fit cost cancels)
    sps_reg = _marginal_fit_sps(
        m, dict(labeled_points=lp, batch_size=32, validation_split=0.0,
                categorical=False), len(x), 1, 1 + 20 * epochs)
    sps_cls = _marginal_fit_sps(
        mc, dict(labeled_points=lpi, batch_size=16, validation_split=0.0,
                 categorical=True, nb_classes=3), len(xi), 1,
        1 + 20 * epochs)
    sc.stop()
    fmt = lambda v: f"{v:,.0f} sps" if v else "below timer floor"
    log(f"config4 boston mse {mse:.4f} ({dt_reg:.1f}s incl. compile; "
        f"marginal {fmt(sps_reg)}), iris acc {acc:.4f} "
        f"({dt_cls:.1f}s; marginal {fmt(sps_cls)})")
    return {
        "boston_mse_normalized": round(mse, 4),
        "boston_fit_seconds_incl_compile": round(dt_reg, 2),
        "boston_samples_per_sec_marginal":
            round(sps_reg, 1) if sps_reg else None,
        "iris_accuracy": round(acc, 4),
        "iris_fit_seconds_incl_compile": round(dt_cls, 2),
        "iris_samples_per_sec_marginal":
            round(sps_cls, 1) if sps_cls else None,
    }


def config5_hyperparam():
    """Distributed TPE search wall-clock (device-slice fan-out).

    Round 5 adds the marginal seconds/trial: differencing searches at two
    ``max_evals`` budgets cancels the fixed setup (context, first-model
    compile). Per-trial recompiles remain — the search space varies layer
    sizes, so each trial IS a new geometry; the marginal figure prices a
    trial's true cost, not the harness's."""
    from elephas_tpu import HyperParamModel
    from elephas_tpu.data import SparkContext

    from hyperparam_optimization import data, model

    evals = int(os.environ.get("BENCH_ALL_EVALS", 2))
    workers = 4
    sc = SparkContext(master=f"local[{workers}]", appName="bench_all_c5")
    hp = HyperParamModel(sc, num_workers=workers)
    t0 = time.perf_counter()
    trials = hp.compute_trials(model=model, data=data, max_evals=evals)
    dt = time.perf_counter() - t0
    e_hi = 3 * evals
    t0 = time.perf_counter()
    trials_hi = hp.compute_trials(model=model, data=data, max_evals=e_hi)
    dt_hi = time.perf_counter() - t0
    n_lo = len(trials)
    n_hi = len(trials_hi)
    marg_trial = (dt_hi - dt) / max(n_hi - n_lo, 1)
    sc.stop()
    ok = [t for t in trials if t["status"] == "ok"]
    best = min(t["loss"] for t in ok)
    devices = sorted({t["device"] for t in trials})
    log(f"config5 search: {n_lo} trials / {workers} workers in "
        f"{dt:.1f}s (incl. compile); marginal {marg_trial:.2f} s/trial "
        f"({n_hi - n_lo} extra trials in {dt_hi - dt:.1f}s); best loss "
        f"{best:.4f}, devices {devices}")
    return {
        "trials": n_lo,
        "workers": workers,
        "wall_seconds_incl_compile": round(dt, 2),
        "marginal_seconds_per_trial": round(marg_trial, 2),
        "best_loss": round(best, 4),
        "distinct_devices": len(devices),
    }


def conv_train_flops_per_sample(model) -> float:
    """Analytic training FLOPs per sample for a Keras conv net — matmul/conv
    FLOPs only (the MFU convention, same rigor as ``bench.py``'s
    ``lm_train_flops_per_token``): a Conv2D costs ``2·kh·kw·cin·cout·Ho·Wo``
    forward (each output pixel is a ``kh·kw·cin``-deep dot), a Dense
    ``2·cin·cout``; training ≈ 3x forward (backward is two conv-sized
    contractions). BN/ReLU/pool are bandwidth, not FLOPs, and are excluded.
    """
    import keras

    fwd = 0.0
    for layer in model.layers:
        if isinstance(layer, keras.layers.Conv2D):
            kh, kw = layer.kernel_size
            cin = int(layer.input.shape[-1])
            _, ho, wo, cout = layer.output.shape
            fwd += 2.0 * kh * kw * cin * cout * ho * wo
        elif isinstance(layer, keras.layers.Dense):
            fwd += 2.0 * int(layer.input.shape[-1]) * int(layer.units)
    return 3.0 * fwd


def config6_conv_mfu():
    """FLOPs-accounted ResNet-50 training throughput + MFU, remat on/off.

    The LM benchmark carries the chip's efficiency story; this config gives
    conv workloads the same rigor: analytic conv FLOPs (above), steady-state
    samples/sec through the compiled engine, MFU against the spec-sheet
    peak, and the cost of rematerialization (recompute-in-backward) on the
    identical geometry. Gated to TPU by default (BENCH_ALL_CONV=1 forces —
    an MFU against a CPU has no meaning). Input size via
    BENCH_ALL_CONV_IMAGE (default 64: CIFAR-class images keep the
    compile tractable; the per-sample FLOPs accounting makes the number
    comparable across sizes).
    """
    import jax
    import keras
    import numpy as np

    from elephas_tpu import SparkModel
    from elephas_tpu.data import SparkContext
    from elephas_tpu.utils import to_simple_rdd

    gate = os.environ.get("BENCH_ALL_CONV", "auto")
    on_tpu = jax.devices()[0].platform == "tpu"
    if gate == "0" or (gate == "auto" and not on_tpu):
        log("config6 conv: skipped (not on TPU; BENCH_ALL_CONV=1 forces)")
        return {"skipped": "not on TPU"}

    from bench import peak_bf16_flops

    img = int(os.environ.get("BENCH_ALL_CONV_IMAGE", 64))
    n = int(os.environ.get("BENCH_ALL_CONV_SAMPLES", 2048))
    batch = int(os.environ.get("BENCH_ALL_CONV_BATCH", 64))
    n_dev = jax.local_device_count()

    rng = np.random.default_rng(0)
    x = rng.uniform(0, 1, size=(n, img, img, 3)).astype("float32")
    y = np.eye(10, dtype="float32")[rng.integers(0, 10, size=n)]
    sc = SparkContext(master=f"local[{n_dev}]", appName="bench_all_c6")
    rdd = to_simple_rdd(sc, x, y, num_slices=n_dev)

    def make_resnet():
        model = keras.applications.ResNet50(
            weights=None, input_shape=(img, img, 3), classes=10)
        model.compile(optimizer="sgd", loss="categorical_crossentropy")
        return model

    flops_sample = conv_train_flops_per_sample(make_resnet())
    peak = peak_bf16_flops(jax.devices()[0])
    out = {"flops_per_sample": round(flops_sample),
           "image": img, "batch": batch}

    # A fit's wall-clock includes the per-fit host<->device round-trip
    # of the ~100 MB ResNet-50 state, which is not per-step work. So two
    # figures are reported: raw steady-state samples/sec, and the MARGINAL
    # per-step cost from differencing a 1-epoch and a 3-epoch fit — the
    # fixed per-fit transfer cancels, leaving the compiled program's
    # actual per-step time, which is what MFU is computed from.
    e_lo, e_hi = 1, 3

    def best_fit_time(sm, epochs, reps=2):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            sm.fit(rdd, epochs=epochs, batch_size=batch, verbose=0,
                   validation_split=0.0)
            best = min(best, time.perf_counter() - t0)
        return best

    # Match the engine's actual schedule: S = ceil(per-worker samples / B)
    # (engine.py pads the last batch), and never 0 — a huge BENCH_ALL_CONV
    # batch must not zero-divide the marginal-step math.
    steps_per_epoch = max(1, -(-(n // n_dev) // batch))
    for name, remat in (("remat_off", False), ("remat_on", True)):
        sm = SparkModel(make_resnet(), mode="synchronous", num_workers=n_dev,
                        remat=remat)
        sm.fit(rdd, epochs=e_lo, batch_size=batch, verbose=0,
               validation_split=0.0)  # warmup/compile @ e_lo
        t_lo = best_fit_time(sm, e_lo)
        sm.fit(rdd, epochs=e_hi, batch_size=batch, verbose=0,
               validation_split=0.0)  # warmup/compile @ e_hi
        t_hi = best_fit_time(sm, e_hi)
        sps_raw = n * e_lo / t_lo / n_dev
        step_ms = max(t_hi - t_lo, 1e-9) / ((e_hi - e_lo) * steps_per_epoch)
        sps_marginal = batch / step_ms
        cell = {
            "samples_per_sec_per_chip_raw": round(sps_raw, 1),
            "marginal_step_ms": round(step_ms * 1e3, 1),
            "samples_per_sec_per_chip_marginal": round(sps_marginal, 1),
        }
        if peak:
            cell["mfu_marginal"] = round(
                flops_sample * sps_marginal / peak, 4)
        out[name] = cell
        log(f"config6 resnet50@{img} {name}: raw {sps_raw:,.0f} sps/chip; "
            f"marginal {step_ms * 1e3:.0f} ms/step = {sps_marginal:,.0f} "
            f"sps/chip, {flops_sample * sps_marginal / 1e12:.1f} TFLOP/s"
            + (f", MFU {cell['mfu_marginal'] * 100:.1f}%" if peak else ""))
    sc.stop()
    return out


def config7_speculative():
    """Speculative decoding measured on a trained draft/target pair.

    Random-weight models never agree, so acceptance is meaningless there;
    this config trains BOTH models on the same synthetic Markov language
    (next token = deterministic map of the current with prob q, else
    uniform noise — learnable in a few hundred steps) and then measures,
    for greedy decoding of held-out prompts:

    - ``acceptance_rate``: accepted draft proposals / proposed;
    - ``seq_pass_reduction``: n_new / verify rounds — the ALGORITHMIC win
      (sequential target passes saved), dispatch-environment-independent;
    - measured wall tokens/sec for plain cached decode vs speculative.

    Since round 4 the greedy round loop is ONE compiled while_loop
    (``_spec_rollout_device``): dispatches per emitted token < 1, so wall
    clock measures the on-chip trade directly. Two wall cells: the small
    trained pair (d512 target — launch-bound decode, where speculation
    buys little by construction) and a SERVING-SCALE pair (d2048/L8
    target, the judged-LM geometry, whose decode step is weight-bandwidth
    bound — the regime speculative decoding exists for). TPU-gated
    (BENCH_ALL_SPEC=1 forces).
    """
    import jax
    import numpy as np
    import optax

    gate = os.environ.get("BENCH_ALL_SPEC", "auto")
    on_tpu = jax.devices()[0].platform == "tpu"
    if gate == "0" or (gate == "auto" and not on_tpu):
        log("config7 speculative: skipped (not on TPU; BENCH_ALL_SPEC=1 "
            "forces)")
        return {"skipped": "not on TPU"}

    from elephas_tpu.models import (
        TransformerLM, build_lm_train_step, build_mesh_sp, make_lm_batches,
        shard_lm_batch,
    )

    V, T, q = 256, 128, 0.9
    steps = int(os.environ.get("BENCH_ALL_SPEC_STEPS", 150))
    n_new = int(os.environ.get("BENCH_ALL_SPEC_NEW", 128))
    spec_k = int(os.environ.get("BENCH_ALL_SPEC_K", 4))
    rng = np.random.default_rng(0)

    def chain(b, t, seed):
        r = np.random.default_rng(seed)
        rows = np.empty((b, t), np.int64)
        rows[:, 0] = r.integers(0, V, size=b)
        nxt = (np.arange(V) * 7 + 13) % V  # the deterministic successor map
        for j in range(1, t):
            noise = r.integers(0, V, size=b)
            take = r.random(b) < q
            rows[:, j] = np.where(take, nxt[rows[:, j - 1]], noise)
        return rows

    mesh = build_mesh_sp(data=1, seq=1)

    def train(model, seed, n_steps, lr=3e-3):
        step, opt_init = build_lm_train_step(
            model, mesh, optax.adam(lr), attn="flash")
        params = model.shard_params(mesh, model.init(seed=seed))
        state = opt_init(params)
        loss = None
        for i in range(n_steps):
            rows = chain(16, T + 1, seed=1000 + i)
            batch = shard_lm_batch(mesh, *make_lm_batches(rows))
            params, state, loss = step(params, state, *batch)
        log(f"config7: trained {n_steps} steps "
            f"(final loss {float(loss):.3f})")
        return params

    horizon = 32 + n_new + spec_k + 2
    target = TransformerLM(vocab=V, d_model=512, n_heads=4, n_layers=4,
                           d_ff=2048, max_len=max(T, horizon),
                           compute_dtype="bfloat16", pos_encoding="rotary")
    draftm = TransformerLM(vocab=V, d_model=128, n_heads=1, n_layers=2,
                           d_ff=512, max_len=max(T, horizon),
                           compute_dtype="bfloat16", pos_encoding="rotary")
    # The draft trains on a THIRD of the steps: a fully-converged draft on
    # this near-deterministic language accepts ~100% (both models argmax
    # the successor map), which demonstrates the mechanism but never
    # exercises rejection — an undertrained draft gives an acceptance rate
    # that actually discriminates.
    t_params = train(target, 0, steps)
    d_params = train(draftm, 1, max(steps // 3, 1))

    prompt = chain(1, 32, seed=99).astype(np.int32)

    # plain cached decode (one compiled scan) — warmup then best-of-2
    plain = None
    t_plain = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        plain = np.asarray(target.generate(t_params, prompt, n_new))
        dt = time.perf_counter() - t0
        t_plain = min(t_plain, dt)  # first rep absorbs compile
    # speculative — same schedule
    stats = None
    t_spec = float("inf")
    spec = None
    for _ in range(3):
        t0 = time.perf_counter()
        spec, stats = target.generate_speculative(
            t_params, prompt, n_new, draftm, d_params, spec_k=spec_k,
            with_stats=True)
        dt = time.perf_counter() - t0
        t_spec = min(t_spec, dt)
    spec = np.asarray(spec)
    agree = bool((spec == plain).all())  # greedy: must match the target

    # Sampled cell: greedy acceptance is STRUCTURALLY ~1.0 on this language
    # (both models argmax the same learned successor map), so the rejection
    # rule never fires; at temperature the acceptance rate is the measured
    # draft/target distribution overlap — the discriminating number.
    _, s_stats = target.generate_speculative(
        t_params, prompt, n_new, draftm, d_params, spec_k=spec_k,
        temperature=0.8, with_stats=True)

    out = {
        "acceptance_rate_greedy": round(stats["acceptance_rate"], 4),
        "acceptance_rate_sampled_t0.8": round(
            s_stats["acceptance_rate"], 4),
        "rounds": stats["rounds"],
        "n_new": n_new,
        "seq_pass_reduction": round(n_new / stats["rounds"], 2),
        "seq_pass_reduction_sampled": round(
            n_new / s_stats["rounds"], 2),
        "spec_k": spec_k,
        "plain_tokens_per_sec": round(n_new / t_plain, 1),
        "spec_tokens_per_sec": round(n_new / t_spec, 1),
        "wall_speedup": round(t_plain / t_spec, 3),
        "greedy_output_matches_target": agree,
    }
    log(f"config7: acceptance {out['acceptance_rate_greedy']:.2%} greedy / "
        f"{out['acceptance_rate_sampled_t0.8']:.2%} sampled, "
        f"{stats['rounds']} verify rounds for {n_new} tokens "
        f"({out['seq_pass_reduction']}x fewer sequential target passes; "
        f"{out['seq_pass_reduction_sampled']}x sampled), "
        f"wall {out['plain_tokens_per_sec']:.0f} -> "
        f"{out['spec_tokens_per_sec']:.0f} tok/s "
        f"(x{out['wall_speedup']}), match={agree}")

    # -- serving-scale cell: big (weight-bandwidth-bound) target ----------
    # d2048/L8 needs ~300 adam(1e-3) steps to learn the Markov language
    # (loss ~0.9; an undertrained target disagrees with ANY draft and
    # acceptance collapses). Wall clock is measured two ways: raw at
    # n_big tokens, and MARGINAL (differencing 64- and n_big-token
    # rollouts) so the fixed per-call launch overhead cancels — the same
    # honest-metric discipline as the judged MNIST figure.
    big_steps = int(os.environ.get("BENCH_ALL_SPEC_BIG_STEPS", 300))
    n_big = int(os.environ.get("BENCH_ALL_SPEC_BIG_NEW", 512))
    bh = 64 + n_big + spec_k + 2
    big = TransformerLM(vocab=V, d_model=2048, n_heads=8, n_layers=8,
                        d_ff=8192, max_len=max(T, bh),
                        compute_dtype="bfloat16", pos_encoding="rotary",
                        tie_embeddings=True)
    bdraft = TransformerLM(vocab=V, d_model=256, n_heads=2, n_layers=2,
                           d_ff=1024, max_len=max(T, bh),
                           compute_dtype="bfloat16", pos_encoding="rotary")
    b_params = train(big, 2, big_steps, lr=1e-3)
    bd_params = train(bdraft, 3, max(big_steps // 3, 1))

    def best_wall(fn):
        best, result = float("inf"), None
        for _ in range(3):
            t0 = time.perf_counter()
            result = np.asarray(fn())
            best = min(best, time.perf_counter() - t0)
        return best, result

    t_plain_64, _ = best_wall(lambda: big.generate(b_params, prompt, 64))
    tb_plain, bplain = best_wall(
        lambda: big.generate(b_params, prompt, n_big))
    t_spec_64, _ = best_wall(lambda: big.generate_speculative(
        b_params, prompt, 64, bdraft, bd_params, spec_k=spec_k))
    tb_spec, bspec = best_wall(lambda: big.generate_speculative(
        b_params, prompt, n_big, bdraft, bd_params, spec_k=spec_k))
    _, bstats = big.generate_speculative(
        b_params, prompt, n_big, bdraft, bd_params, spec_k=spec_k,
        with_stats=True)
    # Sampled cell (round 5): the f32 rejection rule now runs in the SAME
    # compiled round loop — measured with the same marginal differencing.
    # Tokens/sec also reflects the LOWER sampled acceptance (more verify
    # rounds — semantics, not dispatch), so the per-ROUND time is the
    # apples-to-apples device-loop comparison.
    t_sspec_64, _ = best_wall(lambda: big.generate_speculative(
        b_params, prompt, 64, bdraft, bd_params, spec_k=spec_k,
        temperature=0.8, seed=1))
    tb_sspec, _ = best_wall(lambda: big.generate_speculative(
        b_params, prompt, n_big, bdraft, bd_params, spec_k=spec_k,
        temperature=0.8, seed=1))
    _, sstats_64 = big.generate_speculative(
        b_params, prompt, 64, bdraft, bd_params, spec_k=spec_k,
        temperature=0.8, seed=1, with_stats=True)
    _, sbstats = big.generate_speculative(
        b_params, prompt, n_big, bdraft, bd_params, spec_k=spec_k,
        temperature=0.8, seed=1, with_stats=True)
    bagree = bool((np.asarray(bspec) == bplain).all())
    marg = n_big - 64
    m_plain = (tb_plain - t_plain_64) / marg * 1e3  # ms/token
    m_spec = (tb_spec - t_spec_64) / marg * 1e3
    m_sspec = (tb_sspec - t_sspec_64) / marg * 1e3
    _, bstats_64 = big.generate_speculative(
        b_params, prompt, 64, bdraft, bd_params, spec_k=spec_k,
        with_stats=True)
    g_round_ms = (tb_spec - t_spec_64) / max(
        bstats["rounds"] - bstats_64["rounds"], 1) * 1e3
    s_round_ms = (tb_sspec - t_sspec_64) / max(
        sbstats["rounds"] - sstats_64["rounds"], 1) * 1e3
    out["serving_scale"] = {
        "target": "d2048xL8xF8192-bf16",
        "draft": "d256xL2xF1024-bf16",
        "n_new": n_big,
        "acceptance_rate_greedy": round(bstats["acceptance_rate"], 4),
        "rounds": bstats["rounds"],
        "plain_tokens_per_sec": round(n_big / tb_plain, 1),
        "spec_tokens_per_sec": round(n_big / tb_spec, 1),
        "wall_speedup": round(tb_plain / tb_spec, 3),
        "marginal_ms_per_token_plain": round(m_plain, 3),
        "marginal_ms_per_token_spec": round(m_spec, 3),
        "marginal_wall_speedup": (
            round(m_plain / m_spec, 2) if m_spec > 0 else None),
        "greedy_output_matches_target": bagree,
        "sampled_t0.8": {
            "acceptance_rate": round(sbstats["acceptance_rate"], 4),
            "rounds": sbstats["rounds"],
            "marginal_ms_per_token": round(m_sspec, 3),
            "marginal_wall_speedup_vs_plain": (
                round(m_plain / m_sspec, 2) if m_sspec > 0 else None),
            "round_ms_greedy": round(g_round_ms, 2),
            "round_ms_sampled": round(s_round_ms, 2),
            "round_time_ratio_sampled_over_greedy": (
                round(s_round_ms / g_round_ms, 3) if g_round_ms > 0
                else None),
        },
    }
    s = out["serving_scale"]
    ss = s["sampled_t0.8"]
    log(f"config7 serving-scale: acceptance "
        f"{s['acceptance_rate_greedy']:.2%}, wall "
        f"{s['plain_tokens_per_sec']:.0f} -> "
        f"{s['spec_tokens_per_sec']:.0f} tok/s (x{s['wall_speedup']}); "
        f"marginal {m_plain:.2f} -> {m_spec:.2f} ms/tok "
        f"(x{s['marginal_wall_speedup']}), match={bagree}; sampled t0.8 "
        f"{m_sspec:.2f} ms/tok (x{ss['marginal_wall_speedup_vs_plain']} "
        f"vs plain), round {s_round_ms:.1f} vs greedy {g_round_ms:.1f} ms "
        f"(x{ss['round_time_ratio_sampled_over_greedy']})")
    return out


def config8_moe_lm():
    """Mixtral-shaped MoE LM training throughput + model-FLOPs MFU.

    One chip holds ALL experts (the expert axis has size 1 here; multi-chip
    shards them — ``dryrun_multichip``), so this measures the routing
    machinery's single-chip cost: tokens/sec and an MFU whose denominator
    counts MODEL FLOPs only (attention + router + the k ACTIVE experts per
    token, swiglu-aware) — dispatch (index-form slot gather since round 4;
    see docs/PERFORMANCE.md config 8) is counted as OVERHEAD, not useful
    FLOPs, so the gap between this MFU and the dense LM's at equal active
    FLOPs IS the price of routing. TPU-gated (BENCH_ALL_MOE=1 forces).
    """
    import jax
    import numpy as np
    import optax

    gate = os.environ.get("BENCH_ALL_MOE", "auto")
    on_tpu = jax.devices()[0].platform == "tpu"
    if gate == "0" or (gate == "auto" and not on_tpu):
        log("config8 moe: skipped (not on TPU; BENCH_ALL_MOE=1 forces)")
        return {"skipped": "not on TPU"}

    from elephas_tpu.models import (
        MoETransformerLM, adam_compact, build_lm_train_step, build_mesh_sp,
        make_lm_batches, shard_lm_batch,
    )

    D, L, H, F = 1024, 4, 8, 4096
    E, K = 8, 2
    V, T, B = 8192, 1024, 4
    steps, reps = 10, 3
    # param_dtype="bfloat16": expert stacks STORED bf16 (router/attention
    # stay f32; adam math stays f32 via adam_compact upcasts). Kills the
    # dominant per-step f32→bf16 convert traffic — measured −10.1 ms/step
    # at this geometry with the loss trajectory matching f32 storage to
    # 5 decimals at step 2 (docs/PERFORMANCE.md config 8).
    model = MoETransformerLM(
        vocab=V, d_model=D, n_heads=H, n_layers=L, d_ff=F, max_len=T,
        n_experts=E, k=K, capacity_factor=1.25, compute_dtype="bfloat16",
        pos_encoding="rotary", tie_embeddings=True, activation="swiglu",
        norm="rmsnorm", ffn_bias=False, param_dtype="bfloat16",
    )
    mesh = build_mesh_sp(data=1, seq=1)
    step, opt_init = build_lm_train_step(model, mesh, adam_compact(1e-3),
                                         attn="flash")
    params = model.shard_params(mesh, model.init(seed=0))
    state = opt_init(params)
    rows = np.random.default_rng(0).integers(0, V, size=(B, T + 1))
    batch = shard_lm_batch(mesh, *make_lm_batches(rows))

    log(f"config8 moe: d{D} L{L} E{E} k{K} F{F} T{T} B{B} bf16 swiglu "
        "(compiling...)")
    for _ in range(2):
        params, state, loss = step(params, state, *batch)
    float(loss)

    best = float("inf")
    for rep in range(reps):
        t0 = time.perf_counter()
        for _ in range(steps):
            params, state, loss = step(params, state, *batch)
        last = float(loss)
        dt = time.perf_counter() - t0
        log(f"config8 rep {rep}: {dt / steps * 1e3:.1f} ms/step")
        best = min(best, dt)
    assert np.isfinite(last), last

    # model FLOPs/token (fwd, x3 train): attention qkvo + causal dots,
    # router D*E, k active swiglu experts (3 matmuls each), tied head
    attn = L * (2 * (2 * D * D + 2 * D * D) + 4 * D * (T + 1) / 2)
    ffn = L * (2 * D * E + K * 3 * 2 * D * F)
    flops_tok = 3.0 * (attn + ffn + 2 * D * V)
    tok_s = B * T * steps / best
    import bench as _bench
    peak = _bench.peak_bf16_flops(jax.devices()[0])
    mfu = flops_tok * tok_s / peak if peak else None
    log(f"config8 moe: {tok_s:,.0f} tok/s, "
        f"{flops_tok * tok_s / 1e12:.1f} TF/s model flops"
        + (f", MFU {mfu * 100:.1f}%" if mfu else ""))
    return {
        "tokens_per_sec": round(tok_s, 1),
        "model_flops_mfu": round(mfu, 4) if mfu else None,
        "step_ms": round(best / steps * 1e3, 2),
        "flops_per_token_model_only": round(flops_tok),
        "active_params_per_token_frac": round(K / E, 3),
        "config": f"d{D}xL{L}xE{E}k{K}xF{F}xT{T}xB{B}-swiglu-bf16-bf16params",
    }


def config9_large_vocab_lm():
    """V=32k LM: the vocab-chunked loss head vs the dense head.

    The imported-checkpoint vocabs (32k–152k) make the ``[B, T, V]``
    logits + cotangent the peak-memory term of a fine-tuning step.
    ``vocab_block`` streams the head (online-lse forward, per-block
    recompute backward; ``chunked_summed_xent``) — this config measures
    BOTH step time and XLA's compiled temp-memory budget for the two
    paths at d1024/L4/V32768/T2048/B4 bf16. TPU-gated
    (BENCH_ALL_VOCAB=1 forces).
    """
    import jax
    import numpy as np

    gate = os.environ.get("BENCH_ALL_VOCAB", "auto")
    on_tpu = jax.devices()[0].platform == "tpu"
    if gate == "0" or (gate == "auto" and not on_tpu):
        log("config9 vocab: skipped (not on TPU; BENCH_ALL_VOCAB=1 forces)")
        return {"skipped": "not on TPU"}

    from elephas_tpu.models import (
        TransformerLM, adam_compact, build_lm_train_step, build_mesh_sp,
        make_lm_batches, shard_lm_batch,
    )

    D, L, H, F, V, T, B = 1024, 4, 8, 4096, 32768, 2048, 4
    steps = 8
    out = {}
    for label, vocab_block in (("dense_head", None), ("chunked_head", 8192)):
        model = TransformerLM(
            vocab=V, d_model=D, n_heads=H, n_layers=L, d_ff=F, max_len=T,
            compute_dtype="bfloat16", pos_encoding="rotary",
            tie_embeddings=True, activation="swiglu", norm="rmsnorm",
            ffn_bias=False,
        )
        mesh = build_mesh_sp(data=1, seq=1)
        step, opt_init = build_lm_train_step(
            model, mesh, adam_compact(1e-3), attn="flash",
            vocab_block=vocab_block)
        params = model.shard_params(mesh, model.init(seed=0))
        state = opt_init(params)
        rows = np.random.default_rng(0).integers(0, V, size=(B, T + 1))
        batch = shard_lm_batch(mesh, *make_lm_batches(rows))
        temp_gb = None
        try:  # compiled temp budget — the memory claim, measured by XLA
            target = next(v for c in (step.__closure__ or [])
                          for v in [c.cell_contents] if hasattr(v, "lower"))
            compiled = target.lower(params, state, *batch).compile()
            temp_gb = compiled.memory_analysis().temp_size_in_bytes / 1e9
        except Exception as e:
            log(f"config9: memory_analysis unavailable ({e})")
        for _ in range(2):
            params, state, loss = step(params, state, *batch)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(steps):
            params, state, loss = step(params, state, *batch)
        last = float(loss)
        dt = (time.perf_counter() - t0) / steps
        assert np.isfinite(last), last
        out[label] = {
            "tokens_per_sec": round(B * T / dt, 1),
            "step_ms": round(dt * 1e3, 2),
            "xla_temp_gb": round(temp_gb, 2) if temp_gb else None,
        }
        log(f"config9 {label}: {B * T / dt:,.0f} tok/s, "
            f"{dt * 1e3:.1f} ms/step, temp {temp_gb and round(temp_gb, 2)} GB")
    d, c = out["dense_head"], out["chunked_head"]
    if d["xla_temp_gb"] and c["xla_temp_gb"]:
        out["temp_memory_saved_gb"] = round(
            d["xla_temp_gb"] - c["xla_temp_gb"], 2)
    out["config"] = f"d{D}xL{L}xV{V}xT{T}xB{B}-swiglu-bf16"
    return out


def main():
    from harness_env import place_compile_cache

    place_compile_cache()
    results = {}
    failed = []
    for name, fn in (
        ("mnist_cnn_modes", config2_mnist_cnn),
        ("imdb_lstm_pipeline", config3_imdb_lstm),
        ("mllib", config4_mllib),
        ("hyperparam_search", config5_hyperparam),
        ("conv_mfu", config6_conv_mfu),
        ("speculative", config7_speculative),
        ("moe_lm", config8_moe_lm),
        ("large_vocab_lm", config9_large_vocab_lm),
    ):
        try:
            results[name] = fn()
        except Exception as e:  # each config stands alone, then exit != 0
            log(f"{name} FAILED:\n{traceback.format_exc()}")
            results[name] = {"error": f"{type(e).__name__}: {e}"}
            failed.append(name)
    print(json.dumps({"configs": results}))
    if failed:
        log(f"bench_all: {len(failed)} config(s) failed: {', '.join(failed)}")
        sys.exit(1)


if __name__ == "__main__":
    main()
