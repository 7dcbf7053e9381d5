"""Callback parity: BatchEnd/EpochEnd + the tf.keras shim, exercised with
TENSORFLOW PRESENT (reference `maggy/callbacks.py:20-66` is Keras-only; our
shim must actually drive a real keras fit loop, not just import)."""

import numpy as np
import pytest

from maggy_tpu.callbacks import BatchEnd, EpochEnd, keras_reporter_callbacks
from maggy_tpu.core.reporter import Reporter
from maggy_tpu.exceptions import EarlyStopException


class TestNativeCallbacks:
    def test_batch_end_reports_with_running_step(self):
        rep = Reporter()
        rep.reset(trial_id="t")
        cb = BatchEnd(rep, metric="loss")
        cb({"loss": 0.5})
        cb({"loss": 0.25})
        data = rep.get_data()
        assert data["metric"] == 0.25 and data["step"] == 1

    def test_epoch_end_uses_given_step(self):
        rep = Reporter()
        rep.reset(trial_id="t")
        cb = EpochEnd(rep, metric="acc")
        cb({"acc": 0.8}, step=3)
        assert rep.get_data() == {"metric": 0.8, "step": 3, "logs": [],
                                  "trial_id": "t", "span": None,
                                  "newest_step": 3}

    def test_missing_metric_is_skipped(self):
        rep = Reporter()
        rep.reset(trial_id="t")
        BatchEnd(rep, metric="nope")({"loss": 1.0})
        assert rep.get_data()["metric"] is None


class TestKerasShim:
    @pytest.fixture
    def tf(self):
        return pytest.importorskip("tensorflow")

    @pytest.fixture
    def keras_fit(self, tf):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(64, 4)).astype(np.float32)
        y = (X.sum(axis=1) > 0).astype(np.int32)

        def fit(callbacks, epochs=2):
            model = tf.keras.Sequential([
                tf.keras.layers.Dense(8, activation="relu"),
                tf.keras.layers.Dense(2),
            ])
            model.compile(
                optimizer="sgd",
                loss=tf.keras.losses.SparseCategoricalCrossentropy(
                    from_logits=True))
            model.fit(X, y, epochs=epochs, batch_size=16, verbose=0,
                      callbacks=callbacks)

        return fit

    def test_epoch_metric_streams_through_reporter(self, keras_fit):
        rep = Reporter()
        rep.reset(trial_id="t")
        cbs = keras_reporter_callbacks(rep, epoch_metric="loss")
        keras_fit(cbs, epochs=3)
        data = rep.get_data()
        assert data["metric"] is not None
        assert data["step"] == 2  # last epoch index

    def test_batch_metric_streams_through_reporter(self, keras_fit):
        rep = Reporter()
        rep.reset(trial_id="t")
        cbs = keras_reporter_callbacks(rep, batch_metric="loss",
                                       epoch_metric=None)
        keras_fit(cbs, epochs=1)
        data = rep.get_data()
        assert data["metric"] is not None
        assert data["step"] == 3  # 64 samples / batch 16 -> 4 batches

    def test_early_stop_surfaces_inside_keras_fit(self, tf, keras_fit):
        """The driver's STOP arrives between keras batches: the shim's next
        broadcast raises EarlyStopException out of model.fit, exactly like
        the reference's KerasBatchEnd (`callbacks.py:20-43`)."""
        rep = Reporter()
        rep.reset(trial_id="t")
        cbs = keras_reporter_callbacks(rep, batch_metric="loss",
                                       epoch_metric=None)

        class Arm(tf.keras.callbacks.Callback):
            def on_train_batch_end(self, batch, logs=None):
                if batch == 1:
                    rep.early_stop()

        with pytest.raises(EarlyStopException):
            keras_fit([Arm()] + cbs, epochs=2)
