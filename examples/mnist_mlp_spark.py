"""Config 1: MNIST MLP through ``SparkModel.fit``, synchronous mode.

The TPU-native equivalent of the reference's flagship example
(``examples/mnist_mlp_spark.py:~1``): same script shape — build data RDD,
build compiled Keras model, hand both to SparkModel — but training runs as one
XLA program over the device mesh.

Run (TPU): ``KERAS_BACKEND=jax python examples/mnist_mlp_spark.py``
Run (CPU mesh): prefix with
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``
"""

import os
import sys

os.environ.setdefault("KERAS_BACKEND", "jax")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import keras

from elephas_tpu import SparkModel
from elephas_tpu.data import SparkContext
from elephas_tpu.utils import to_simple_rdd

from _datasets import load_mnist  # noqa: E402


def main():
    import jax

    n_workers = jax.local_device_count()
    sc = SparkContext(master=f"local[{n_workers}]", appName="mnist_mlp")
    n_train = int(os.environ.get("EX_SAMPLES", 16384))
    epochs = int(os.environ.get("EX_EPOCHS", 5))
    (x_train, y_train), (x_test, y_test) = load_mnist(n_train=n_train)

    model = keras.Sequential(
        [
            keras.layers.Dense(128, activation="relu"),
            keras.layers.Dropout(0.2),
            keras.layers.Dense(128, activation="relu"),
            keras.layers.Dropout(0.2),
            keras.layers.Dense(10, activation="softmax"),
        ]
    )
    model.build((None, 784))
    model.compile(optimizer="adam", loss="categorical_crossentropy",
                  metrics=["accuracy"])

    rdd = to_simple_rdd(sc, x_train, y_train)
    spark_model = SparkModel(model, mode="synchronous", num_workers=n_workers)
    spark_model.fit(rdd, epochs=epochs, batch_size=128, verbose=1,
                    validation_split=0.1)

    loss, acc = spark_model.evaluate(x_test, y_test)
    print(f"test loss={loss:.4f} acc={acc:.4f}")
    sc.stop()


if __name__ == "__main__":
    main()
