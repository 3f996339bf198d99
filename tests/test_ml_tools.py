"""Tests for the ML estimators, straggler mitigation, kth_largest, and the
ETL/perf tools (reference analogs: DLEstimator/DLClassifier ML-pipeline
specs, the straggler-drop path of DistriOptimizerSpec, Util.kthLargest,
ImageNetSeqFileGenerator)."""

import os

import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.ml import DLClassifier, DLEstimator
from bigdl_tpu.optim import Adam
from bigdl_tpu.utils import kth_largest


def test_kth_largest_matches_sort():
    rng = np.random.default_rng(0)
    vals = rng.standard_normal(101).tolist()
    ranked = sorted(vals, reverse=True)
    for k in (1, 2, 50, 101):
        assert kth_largest(vals, k) == ranked[k - 1]
    with pytest.raises(ValueError):
        kth_largest(vals, 0)
    with pytest.raises(ValueError):
        kth_largest(vals, 102)


def _toy_classification(n=192, d=10, classes=3, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((classes, d)) * 3
    y = np.arange(n) % classes
    X = centers[y] + rng.standard_normal((n, d)) * 0.3
    return X.astype(np.float32), y.astype(np.float32)


def test_dl_classifier_fit_predict_score():
    X, y = _toy_classification()
    model = (nn.Sequential().add(nn.Linear(10, 16)).add(nn.ReLU())
             .add(nn.Linear(16, 3)))
    est = DLClassifier(model, nn.CrossEntropyCriterion(), batch_size=32,
                       max_epoch=8, optim_method=Adam(1e-2))
    fitted = est.fit(X, y)
    preds = fitted.predict(X)
    assert preds.shape == (len(X),)
    assert fitted.score(X, y) > 0.95
    # transform returns raw outputs
    assert fitted.transform(X).shape == (len(X), 3)


def test_dl_estimator_regression():
    rng = np.random.default_rng(1)
    X = rng.standard_normal((128, 5)).astype(np.float32)
    w = rng.standard_normal((5, 1)).astype(np.float32)
    y = X @ w
    est = DLEstimator(nn.Linear(5, 1), nn.MSECriterion(),
                      label_size=(1,), batch_size=32, max_epoch=80,
                      optim_method=Adam(3e-2))
    fitted = est.fit(X, y)
    pred = fitted.transform(X)
    assert pred.shape == (128, 1)
    assert float(np.mean((pred - y) ** 2)) < 0.05


def test_feature_size_reshaping():
    X, y = _toy_classification(d=16)
    model = (nn.Sequential().add(nn.Reshape((16,))).add(nn.Linear(16, 3)))
    est = DLClassifier(model, nn.CrossEntropyCriterion(),
                       feature_size=(4, 4), batch_size=32, max_epoch=5,
                       optim_method=Adam(1e-2))
    fitted = est.fit(X, y)
    assert fitted.predict(X).shape == (len(X),)


def test_straggler_drop_property_validation():
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim import Optimizer
    ds = DataSet.array([Sample(np.zeros(4, np.float32), np.float32(0))] * 8)
    opt = Optimizer(nn.Linear(4, 2), ds.transform(SampleToMiniBatch(4)),
                    nn.CrossEntropyCriterion())
    with pytest.raises(ValueError):
        opt.set_drop_module_property(0.5, 0.2)
    opt.set_drop_module_property(0.1, 0.3, batch_size=10,
                                 warmup_iteration=2)
    assert opt.drop_percentage == 0.1


def test_straggler_check_drops_slow_iterations():
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim import Optimizer
    ds = DataSet.array([Sample(np.zeros(4, np.float32), np.float32(0))] * 8)
    opt = Optimizer(nn.Linear(4, 2), ds.transform(SampleToMiniBatch(4)),
                    nn.CrossEntropyCriterion())
    opt.set_drop_module_property(0.05, 0.5, batch_size=20,
                                 warmup_iteration=5)
    # feed a window of fast iterations, then a straggler
    dropped = []
    for i in range(30):
        dropped.append(opt._straggler_check(0.01, i + 1))
    assert not any(dropped)  # uniform times: nothing above threshold budget
    assert opt._straggler_check(1.0, 31) is True  # clear straggler
    got = opt.metrics.get("dropped iterations")
    assert got[0] == 1.0


def test_straggler_drop_budget_respected():
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim import Optimizer
    ds = DataSet.array([Sample(np.zeros(4, np.float32), np.float32(0))] * 8)
    opt = Optimizer(nn.Linear(4, 2), ds.transform(SampleToMiniBatch(4)),
                    nn.CrossEntropyCriterion())
    opt.set_drop_module_property(0.05, 0.1, batch_size=20,
                                 warmup_iteration=0)
    for i in range(20):
        opt._straggler_check(0.01, i + 1)
    n_dropped = sum(opt._straggler_check(5.0, 21 + i) for i in range(10))
    # max_drop_percentage=0.1 over a 20-wide window caps drops at 2
    assert n_dropped <= 2


def test_straggler_ramping_waits_capped():
    # regression: a monotonically slowing pipeline must not get every
    # iteration dropped — the budget caps drops per threshold window
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim import Optimizer
    ds = DataSet.array([Sample(np.zeros(4, np.float32), np.float32(0))] * 8)
    opt = Optimizer(nn.Linear(4, 2), ds.transform(SampleToMiniBatch(4)),
                    nn.CrossEntropyCriterion())
    opt.set_drop_module_property(0.05, 0.1, batch_size=20,
                                 warmup_iteration=0)
    wait = 0.01
    for i in range(20):
        opt._straggler_check(wait, i + 1)
    dropped = 0
    for i in range(30):
        wait *= 2.0
        dropped += opt._straggler_check(wait, 21 + i)
    # 0.1 * 20 = 2 drops allowed per 20-iteration budget window; 30 iters
    # span at most 2 windows
    assert dropped <= 4


def test_straggler_batch_size_validation():
    from bigdl_tpu.dataset import DataSet, Sample, SampleToMiniBatch
    from bigdl_tpu.optim import Optimizer
    ds = DataSet.array([Sample(np.zeros(4, np.float32), np.float32(0))] * 8)
    opt = Optimizer(nn.Linear(4, 2), ds.transform(SampleToMiniBatch(4)),
                    nn.CrossEntropyCriterion())
    with pytest.raises(ValueError):
        opt.set_drop_module_property(0.1, 0.2, batch_size=1)
    with pytest.raises(ValueError):
        opt.set_drop_module_property(0.1, 0.2, warmup_iteration=-1)


def test_record_generator_end_to_end(tmp_path):
    from bigdl_tpu.tools.record_generator import convert
    from bigdl_tpu.utils.recordio import read_records
    # build a tiny 2-class image tree (PPM — decodable without PIL)
    for cls in ("cat", "dog"):
        os.makedirs(tmp_path / "imgs" / cls)
        for i in range(3):
            arr = np.full((4, 5, 3), 10 * i, np.uint8)
            _write_ppm(str(tmp_path / "imgs" / cls / f"{i}.ppm"), arr)
    out = str(tmp_path / "out" / "train.bdr")
    paths, n = convert(str(tmp_path / "imgs"), out, shards=2, quiet=True)
    assert n == 6 and len(paths) == 2
    recs = list(read_records(out + "-*-of-*"))
    assert len(recs) == 6
    labels = sorted(r["label"] for r in recs)
    assert labels == [0.0, 0.0, 0.0, 1.0, 1.0, 1.0]
    assert recs[0]["data"].shape == (4, 5, 3)
    # pixel VALUES must survive the uint8 storage roundtrip (i=2 -> 20)
    maxes = sorted(int(r["data"].max()) for r in recs)
    assert maxes == [0, 0, 10, 10, 20, 20]
    # and the training loader must rescale uint8 by dtype
    from bigdl_tpu.models.run import _load_samples
    samples = _load_samples(out + "-*-of-*", (4, 5, 3))
    vals = sorted(round(float(s.feature.max()), 4) for s in samples)
    assert vals[-1] == round(20 / 255, 4)


def _write_ppm(path, arr):
    h, w, _ = arr.shape
    with open(path, "wb") as f:
        f.write(b"P6\n%d %d\n255\n" % (w, h))
        f.write(arr.tobytes())


def test_perf_tool_lenet():
    from bigdl_tpu.tools.perf import run
    out = run("lenet", batch_size=8, iters=2, warmup=1)
    assert out["records_per_second"] > 0
    assert out["model"] == "lenet"


# ----------------------------------------------------------------------
# DataFrame column semantics + validation/early stopping (round-2 verdict
# weak #7: DLEstimator.scala:53-109's featuresCol/labelCol/prediction
# contract and validation support)
# ----------------------------------------------------------------------

def _toy_frame(n=96, d=5, classes=3, seed=0):
    pd = pytest.importorskip("pandas")
    r = np.random.default_rng(seed)
    X = r.normal(size=(n, d)).astype(np.float32)
    y = r.integers(0, classes, size=n)
    X[np.arange(n), y] += 2.5  # separable
    df = pd.DataFrame({f"f{i}": X[:, i] for i in range(d)})
    df["label"] = y
    return df, X, y


def test_estimator_fits_from_dataframe_columns():
    df, X, y = _toy_frame()
    model = nn.Sequential().add(nn.Linear(5, 3))
    est = DLClassifier(model, nn.CrossEntropyCriterion(), batch_size=32,
                       max_epoch=30, label_col="label",
                       optim_method=Adam(1e-2))
    fitted = est.fit(df)  # labels resolved from the label column
    acc = fitted.score(df)
    assert acc > 0.8, acc
    out = fitted.transform(df)
    assert "prediction" in out.columns
    assert "prediction" not in df.columns  # transform returns a COPY
    assert np.mean(np.asarray(out["prediction"]) == y) == acc


def test_estimator_explicit_feature_columns():
    df, X, y = _toy_frame()
    est = DLClassifier(nn.Sequential().add(nn.Linear(2, 3)),
                       nn.CrossEntropyCriterion(), batch_size=32,
                       max_epoch=2, features_col=["f0", "f1"])
    fitted = est.fit(df)
    assert fitted.predict(df).shape == (len(df),)


def test_early_stopping_plateau_ends_training():
    """With patience=2 and an EXACTLY constant val loss (lr=0 — the hardest
    plateau), training must end after ~patience+1 validations, not at
    max_epoch=200."""
    from bigdl_tpu.optim import SGD
    df, X, y = _toy_frame(n=64)
    est = DLClassifier(nn.Sequential().add(nn.Linear(5, 3)),
                       nn.CrossEntropyCriterion(), batch_size=32,
                       max_epoch=200,
                       optim_method=SGD(learning_rate=0.0))
    est.set_validation(X, y, early_stopping_patience=2)
    fitted = est.fit(X, y)
    assert fitted is not None
    epochs_run = est.optimizer_.optim_method.hyper["epoch"] - 1
    assert epochs_run <= 5, f"early stopping never fired: {epochs_run} epochs"


def test_plateau_trigger_semantics():
    from bigdl_tpu.optim import Trigger
    # with the validation-observation counter: constant values still count
    t = Trigger.plateau("val_loss", patience=2)
    assert not t({"val_loss": 1.0, "val_obs": 1})  # baseline
    assert not t({"val_loss": 1.0, "val_obs": 1})  # same tick: no-op
    assert not t({"val_loss": 1.0, "val_obs": 2})  # constant: bad 1
    assert t({"val_loss": 1.0, "val_obs": 3})      # constant: bad 2 -> fire
    # without a counter (external state dicts): value-change fallback
    t2 = Trigger.plateau("val_loss", patience=2, counter=None)
    assert not t2({"val_loss": 1.0})
    assert not t2({"val_loss": 0.5})   # improved
    assert not t2({"val_loss": 0.6})   # bad 1
    assert not t2({"val_loss": 0.6})   # unchanged: not a new observation
    assert t2({"val_loss": 0.7})       # bad 2 -> fire
    t3 = Trigger.plateau("score", patience=1, mode="max", counter=None)
    assert not t3({"score": 0.5})
    assert not t3({"score": 0.9})
    assert t3({"score": 0.8})


def test_plateau_trigger_latches_after_firing():
    """Once fired, plateau stays True: the driver polls end triggers at
    several points and a one-shot True could be consumed by the inner-loop
    check without ending training."""
    from bigdl_tpu.optim import Trigger
    t = Trigger.plateau("val_loss", patience=1)
    assert not t({"val_loss": 1.0, "val_obs": 1})
    assert t({"val_loss": 1.0, "val_obs": 2})   # fires
    assert t({"val_loss": 1.0, "val_obs": 2})   # latched, same tick
    assert t({"val_loss": 0.1, "val_obs": 3})   # latched even on improvement


@pytest.mark.slow  # CLI smoke via subprocess-scale work: slow lane
def test_cli_transformer_synthetic_smoke():
    """Train CLI drives the transformer LM workload (token-spec synthetic
    data, TimeDistributedCriterion, per-token Top1 validation)."""
    import sys
    from bigdl_tpu.models import run as run_cli
    argv_save = sys.argv
    try:
        sys.argv = ["run", "train", "--model", "transformer", "--synthetic",
                    "--class-num", "64", "--batch-size", "32",
                    "--max-epoch", "1", "--max-iteration", "3",
                    "--learning-rate", "0.003", "--optim", "adam"]
        opt = run_cli.main()
        assert opt.optim_method.hyper["neval"] > 3
    finally:
        sys.argv = argv_save
