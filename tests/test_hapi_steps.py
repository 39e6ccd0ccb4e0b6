"""hapi.Model jits its three steps directly: the network's forward is
walked once a batch signature, whichever step runs it."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.observability.trace import get_tracer, reset_tracer


def _model():
    pt.seed(42)
    net = pt.nn.Sequential(
        pt.nn.Flatten(), pt.nn.Linear(3 * 8 * 8, 32), pt.nn.ReLU(),
        pt.nn.Linear(32, 4))
    model = pt.Model(net)
    model.prepare(
        optimizer=pt.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters()),
        loss=pt.nn.CrossEntropyLoss())
    return model


# grad_step is what train_batch runs while the tracer is on: backward and
# optimizer as two programs, so that the step-phase spans have a boundary;
# predict_batch is the eval step without labels, a signature of its own
@pytest.mark.parametrize("path", ["train_batch", "eval_batch", "grad_step",
                                  "predict_batch"])
def test_network_forward_is_walked_once_a_signature(path):
    model = _model()
    walks = []
    first = model.network[0]
    inner = first.forward

    def counted(x):
        walks.append(1)
        return inner(x)

    first.forward = counted
    rng = np.random.RandomState(0)

    def batch(n):
        return ([rng.randn(n, 3, 8, 8).astype(np.float32)],
                [rng.randint(0, 4, (n, 1))])

    reset_tracer()
    if path == "grad_step":
        get_tracer().enable()
    run = {"eval_batch": model.eval_batch,
           "predict_batch": lambda x, _: model.predict_batch(x)}.get(
               path, model.train_batch)
    try:
        for _ in range(3):
            run(*batch(8))
        assert len(walks) == 1
        run(*batch(5))
        run(*batch(5))
        assert len(walks) == 2
    finally:
        reset_tracer()
