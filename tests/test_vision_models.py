"""Vision model zoo forward-shape + trainability tests (new families)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.vision import models as M


def _x(batch=1, size=64):
    return paddle.to_tensor(
        np.random.RandomState(0).randn(batch, 3, size, size).astype("float32"))


FACTORIES = [
    ("densenet121", lambda: M.densenet121(num_classes=10)),
    ("squeezenet1_0", lambda: M.squeezenet1_0(num_classes=10)),
    ("squeezenet1_1", lambda: M.squeezenet1_1(num_classes=10)),
    ("shufflenet_v2_x0_25", lambda: M.shufflenet_v2_x0_25(num_classes=10)),
    ("mobilenet_v1_x025", lambda: M.mobilenet_v1(scale=0.25, num_classes=10)),
]


class TestNewModels:
    @pytest.mark.parametrize("name,factory", FACTORIES,
                             ids=[n for n, _ in FACTORIES])
    def test_forward_shape(self, name, factory):
        paddle.seed(0)
        model = factory()
        model.eval()
        out = model(_x())
        assert out.shape == [1, 10]
        assert np.isfinite(out.numpy()).all()

    def test_googlenet_aux_heads(self):
        paddle.seed(0)
        model = M.googlenet(num_classes=10)
        model.train()
        out, a1, a2 = model(_x(size=96))
        assert out.shape == [1, 10] and a1.shape == [1, 10] and a2.shape == [1, 10]
        model.eval()
        assert model(_x(size=96)).shape == [1, 10]

    # eager DenseNet-121 training compiles ~1,400 distinct per-op programs
    # once each (65 ms apiece on the CPU backend): 140-245 s on a loaded
    # 8-core host, over the default limit
    @pytest.mark.time_limit(600)
    def test_densenet_trains(self):
        paddle.seed(0)
        model = M.densenet121(num_classes=4)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=model.parameters())
        x = _x(batch=2, size=32)
        y = paddle.to_tensor(np.array([1, 3], "int64"))
        import paddle_tpu.nn.functional as F

        model.train()
        losses = []
        for _ in range(3):
            loss = F.cross_entropy(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0]

    def test_pretrained_raises(self):
        with pytest.raises(ValueError, match="pretrained"):
            M.densenet121(pretrained=True)
        with pytest.raises(ValueError, match="pretrained"):
            M.shufflenet_v2_x1_0(pretrained=True)

    def test_depth_tables(self):
        assert isinstance(M.densenet169(num_classes=2), M.DenseNet)
        with pytest.raises(ValueError):
            M.DenseNet(layers=123)
        with pytest.raises(ValueError):
            M.ShuffleNetV2(scale=0.7)
