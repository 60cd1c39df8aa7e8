"""Extended vision surface: transforms (color/warp/erase), new model
families, folder datasets (reference python/paddle/vision/)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.vision.transforms as T
from paddle_tpu.vision import datasets, models


def _img(h=12, w=12):
    gy, gx = np.mgrid[0:h, 0:w]
    return np.stack([gy * 20, gx * 20, (gy + gx) * 10], -1).astype("uint8")


class TestColorTransforms:
    def test_adjust_brightness_contrast(self):
        img = _img()
        out = T.adjust_brightness(img, 2.0)
        assert out.dtype == np.uint8 and out.max() == 255
        np.testing.assert_allclose(T.adjust_brightness(img, 1.0), img)
        flat = T.adjust_contrast(img, 0.0)
        assert flat.std() < img.std()

    def test_adjust_saturation_and_grayscale(self):
        img = _img()
        gray = T.to_grayscale(img)
        assert gray.shape == (12, 12, 1)
        g3 = T.to_grayscale(img, 3)
        assert (g3[..., 0] == g3[..., 1]).all()
        desat = T.adjust_saturation(img, 0.0)
        assert (np.abs(desat[..., 0].astype(int)
                       - desat[..., 1].astype(int)) <= 1).all()

    def test_adjust_hue_identity_and_range(self):
        img = _img()
        np.testing.assert_allclose(T.adjust_hue(img, 0.0), img, atol=2)
        with pytest.raises(ValueError):
            T.adjust_hue(img, 0.7)

    def test_colorjitter_runs(self):
        out = T.ColorJitter(0.4, 0.4, 0.4, 0.2)(_img())
        assert out.shape == (12, 12, 3)


class TestGeometric:
    def test_rotate_360_identity(self):
        img = _img()
        out = T.rotate(img, 360.0)
        np.testing.assert_allclose(out.astype(int), img.astype(int), atol=2)

    def test_rotate_90_matches_np(self):
        img = _img(8, 8)
        out = T.rotate(img, 90.0)
        want = np.rot90(img, 1)  # CCW like PIL positive angle
        np.testing.assert_allclose(out.astype(int), want.astype(int), atol=3)

    def test_affine_translate(self):
        img = _img(8, 8)
        out = T.affine(img, 0.0, translate=(2, 0), scale=1.0)
        # content moves right by 2; col 4 now holds old col 2
        np.testing.assert_allclose(out[:, 4].astype(int),
                                   img[:, 2].astype(int), atol=2)

    def test_perspective_identity(self):
        img = _img(8, 8)
        pts = [(0, 0), (7, 0), (7, 7), (0, 7)]
        out = T.perspective(img, pts, pts)
        np.testing.assert_allclose(out.astype(int), img.astype(int), atol=1)

    def test_random_classes_run(self):
        img = _img()
        assert T.RandomRotation(20)(img).shape == img.shape
        assert T.RandomAffine(10, translate=(0.1, 0.1),
                              scale=(0.9, 1.1))(img).shape == img.shape
        assert T.RandomPerspective(prob=1.0)(img).shape == img.shape

    def test_pad_and_erase(self):
        img = _img(6, 6)
        assert T.pad(img, 2).shape == (10, 10, 3)
        er = T.erase(img, 1, 1, 3, 3, 0)
        assert (er[1:4, 1:4] == 0).all()
        # Tensor CHW path
        t = paddle.to_tensor(img.transpose(2, 0, 1).astype("float32"))
        et = T.erase(t, 0, 0, 2, 2, 5.0)
        assert (np.asarray(et._data)[:, :2, :2] == 5.0).all()

    def test_random_erasing(self):
        out = T.RandomErasing(prob=1.0, value=0)(_img(16, 16))
        assert (out == 0).any()


class TestNewModels:
    # one case a factory, so that two workers can take them: together they
    # took 60-105 s of eager compiles on a loaded 8-core host
    @pytest.mark.parametrize("fac", ["mobilenet_v3_large",
                                     "mobilenet_v3_small"])
    def test_mobilenet_v3(self, fac):
        x = paddle.to_tensor(np.random.RandomState(0)
                             .rand(1, 3, 64, 64).astype("float32"))
        m = getattr(models, fac)(num_classes=7)
        m.eval()
        assert list(m(x).shape) == [1, 7]

    def test_resnext_factories(self):
        m = models.resnext50_64x4d(num_classes=4)
        x = paddle.to_tensor(np.random.RandomState(1)
                             .rand(1, 3, 64, 64).astype("float32"))
        m.eval()
        assert list(m(x).shape) == [1, 4]
        assert models.resnext152_32x4d is not None
        assert models.resnext152_64x4d is not None

    def test_shufflenet_swish(self):
        m = models.shufflenet_v2_swish(num_classes=5)
        x = paddle.to_tensor(np.random.RandomState(2)
                             .rand(1, 3, 64, 64).astype("float32"))
        m.eval()
        assert list(m(x).shape) == [1, 5]

    @pytest.mark.slow
    def test_inception_v3(self):
        m = models.inception_v3(num_classes=3)
        x = paddle.to_tensor(np.random.RandomState(3)
                             .rand(1, 3, 299, 299).astype("float32"))
        m.eval()
        assert list(m(x).shape) == [1, 3]


class TestFolderDatasets:
    def _build_tree(self, root):
        from PIL import Image

        for cls in ("cat", "dog"):
            d = root / cls
            d.mkdir()
            for i in range(3):
                Image.fromarray(_img()).save(str(d / f"{i}.png"))

    def test_dataset_folder(self, tmp_path):
        self._build_tree(tmp_path)
        ds = datasets.DatasetFolder(str(tmp_path))
        assert len(ds) == 6
        assert ds.classes == ["cat", "dog"]
        img, label = ds[0]
        assert label == 0 and np.asarray(img).shape == (12, 12, 3)
        img5, label5 = ds[5]
        assert label5 == 1

    def test_image_folder(self, tmp_path):
        self._build_tree(tmp_path)
        ds = datasets.ImageFolder(str(tmp_path))
        assert len(ds) == 6
        (sample,) = ds[0]
        assert np.asarray(sample).shape == (12, 12, 3)

    def test_dataset_folder_with_transform(self, tmp_path):
        self._build_tree(tmp_path)
        ds = datasets.DatasetFolder(
            str(tmp_path),
            transform=lambda im: np.asarray(im).astype("float32") / 255.0)
        img, _ = ds[0]
        assert img.dtype == np.float32 and img.max() <= 1.0

    def test_voc_and_flowers_require_files(self):
        with pytest.raises(ValueError, match="required"):
            datasets.Flowers()
        with pytest.raises(ValueError, match="VOCdevkit"):
            datasets.VOC2012()
