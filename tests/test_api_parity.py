"""2.0 API parity surface (api_diff tool as a CI gate) + functional
checks for the pieces added to reach it: vision.ops deform_conv2d /
yolo_loss / decode_jpeg, fleet data generators, io.get_worker_info,
static/jit shims."""

import os
import subprocess
import sys

import numpy as np
import pytest

import paddle1_tpu as paddle
from paddle1_tpu.core.tensor import to_tensor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _np(t):
    return np.asarray(t.numpy())


class TestApiDiffGate:
    def test_sweep_meets_floors(self):
        """tools/api_diff.py is the api-compat CI check (reference
        tools/check_api_compatible.py role): every namespace must meet
        its pinned floor."""
        if not os.path.isdir("/root/reference"):
            pytest.skip("tools/api_diff.py diffs against the reference "
                        "tree at /root/reference, which is not part of "
                        "this checkout")
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=1",
                   JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable,
                            os.path.join(REPO, "tools", "api_diff.py")],
                           capture_output=True, text=True, env=env,
                           timeout=280)
        assert r.returncode == 0, r.stdout + r.stderr


class TestVisionOps2:
    def test_deform_conv2d_matches_fluid_spelling(self):
        import paddle1_tpu.fluid.layers as L
        from paddle1_tpu.vision.ops import deform_conv2d
        rng = np.random.default_rng(0)
        x = rng.standard_normal((1, 4, 8, 8)).astype(np.float32)
        off = np.zeros((1, 18, 6, 6), np.float32)
        mask = np.ones((1, 9, 6, 6), np.float32)
        # fluid implicit spelling creates the weights; reuse them
        out_fluid = L.deformable_conv(to_tensor(x), to_tensor(off),
                                      to_tensor(mask), 5, 3,
                                      name="parity_dcn")
        import paddle1_tpu.fluid as fluid
        w, b = fluid.layers.implicit_parameters()[-2:]
        out_fn = deform_conv2d(to_tensor(x), to_tensor(off), w, b,
                               mask=to_tensor(mask))
        np.testing.assert_allclose(_np(out_fn), _np(out_fluid),
                                   rtol=1e-5, atol=1e-6)

    def test_DeformConv2D_layer_trains(self):
        from paddle1_tpu.vision.ops import DeformConv2D
        rng = np.random.default_rng(1)
        layer = DeformConv2D(2, 3, 3)
        x = to_tensor(rng.standard_normal((1, 2, 6, 6)).astype(
            np.float32))
        off = to_tensor(np.zeros((1, 18, 4, 4), np.float32))
        out = layer(x, off)
        assert tuple(out.shape) == (1, 3, 4, 4)
        out.sum().backward()
        assert np.abs(_np(layer.weight.grad)).sum() > 0

    def test_DeformConv2D_registers_in_enclosing_layer(self):
        from paddle1_tpu.vision.ops import DeformConv2D

        class Net(paddle.nn.Layer):
            def __init__(self):
                super().__init__()
                self.dcn = DeformConv2D(2, 3, 3)

            def forward(self, x, off):
                return self.dcn(x, off)
        net = Net()
        names = set()
        params = list(net.parameters())
        assert len(params) >= 2  # dcn weight + bias visible
        sd = net.state_dict()
        assert any("dcn" in k for k in sd)

    def test_yolo_loss_smooth_score_scale(self):
        from paddle1_tpu.vision.ops import yolo_loss
        rng = np.random.default_rng(5)
        B, na, C = 1, 3, 4
        x = to_tensor(rng.standard_normal(
            (B, na * (5 + C), 4, 4)).astype(np.float32) * 0.1)
        gt = np.array([[[0.5, 0.5, 0.3, 0.3]]], np.float32)
        gl = np.array([[1]], np.int64)
        kw = dict(anchors=[10, 13, 16, 30, 33, 23],
                  anchor_mask=[0, 1, 2], class_num=C,
                  ignore_thresh=0.7, downsample_ratio=32)
        base = float(np.asarray(yolo_loss(
            x, to_tensor(gt), to_tensor(gl),
            use_label_smooth=False, **kw).numpy()))
        smoothed = float(np.asarray(yolo_loss(
            x, to_tensor(gt), to_tensor(gl),
            use_label_smooth=True, **kw).numpy()))
        assert smoothed != base          # smoothing changes the target
        # gt_score = 0 removes that gt's box/cls contribution
        zeroed = float(np.asarray(yolo_loss(
            x, to_tensor(gt), to_tensor(gl),
            gt_score=to_tensor(np.zeros((1, 1), np.float32)),
            use_label_smooth=False, **kw).numpy()))
        assert zeroed < base
        scaled = float(np.asarray(yolo_loss(
            x, to_tensor(gt), to_tensor(gl), scale_x_y=1.2,
            use_label_smooth=False, **kw).numpy()))
        assert scaled != base            # decode scale shifts targets

    def test_yolo_loss_single_level(self):
        from paddle1_tpu.vision.ops import yolo_loss
        rng = np.random.default_rng(2)
        B, na, C = 2, 3, 4
        x = to_tensor(rng.standard_normal(
            (B, na * (5 + C), 4, 4)).astype(np.float32) * 0.1)
        x.stop_gradient = False
        gt = np.array([[[0.5, 0.5, 0.3, 0.3]],
                       [[0.25, 0.25, 0.2, 0.2]]], np.float32)
        gl = np.array([[1], [2]], np.int64)
        loss = yolo_loss(x, to_tensor(gt), to_tensor(gl),
                         anchors=[10, 13, 16, 30, 33, 23],
                         anchor_mask=[0, 1, 2], class_num=C,
                         ignore_thresh=0.7, downsample_ratio=32)
        v = float(np.asarray(loss.numpy()))
        assert v > 0
        loss.backward()
        assert np.abs(_np(x.grad)).sum() > 0

    def test_decode_jpeg_roundtrip(self, tmp_path):
        from paddle1_tpu.core.jpeg import encode_jpeg_bytes
        from paddle1_tpu.vision.ops import decode_jpeg, read_file
        y, xg = np.mgrid[0:24, 0:32]
        img = np.stack([(xg * 5) % 256, (y * 7) % 256,
                        ((xg + y) * 3) % 256], -1).astype(np.uint8)
        img = img // 8 * 8
        p = tmp_path / "t.jpg"
        p.write_bytes(encode_jpeg_bytes(img, quality=92))
        raw = read_file(str(p))
        out = _np(decode_jpeg(raw))
        assert out.shape == (3, 24, 32)  # CHW like the reference
        err = np.abs(out.transpose(1, 2, 0).astype(float)
                     - img.astype(float))
        assert err.mean() < 8, err.mean()

    def test_decode_jpeg_rejects_progressive(self):
        from paddle1_tpu.vision.ops import decode_jpeg
        # minimal stream with a progressive SOF2 marker
        bad = (b"\xff\xd8\xff\xc2\x00\x0b\x08\x00\x08\x00\x08\x01"
               b"\x01\x11\x00\xff\xd9")
        with pytest.raises(Exception, match="progressive|baseline"):
            decode_jpeg(to_tensor(np.frombuffer(bad, np.uint8).copy()))


class TestDataGenerators:
    def test_multislot_lines(self):
        from paddle1_tpu.distributed.fleet import MultiSlotDataGenerator

        class G(MultiSlotDataGenerator):
            def generate_sample(self, line):
                def it():
                    yield [("ids", [1, 2, 3]), ("label", [0])]
                    yield [("ids", [7]), ("label", [1])]
                return it
        lines = G().run_from_memory()
        assert lines == ["3 1 2 3 1 0\n", "1 7 1 1\n"]

    def test_generate_batch_hook_applies(self):
        from paddle1_tpu.distributed.fleet import MultiSlotDataGenerator

        class G(MultiSlotDataGenerator):
            def generate_sample(self, line):
                def it():
                    for i in range(5):
                        yield [("v", [i])]
                return it

            def generate_batch(self, samples):
                def it():
                    # batch-level transform: offset every value by 100
                    for s in samples:
                        yield [(n, [v + 100 for v in vals])
                               for n, vals in s]
                return it
        g = G()
        g.set_batch(2)
        lines = g.run_from_memory()
        assert lines == ["1 100\n", "1 101\n", "1 102\n", "1 103\n",
                         "1 104\n"]

    def test_multislot_validates_slot_order(self):
        from paddle1_tpu.distributed.fleet import MultiSlotDataGenerator

        class Bad(MultiSlotDataGenerator):
            def generate_sample(self, line):
                def it():
                    yield [("a", [1])]
                    yield [("b", [1])]
                return it
        with pytest.raises(ValueError, match="slot"):
            Bad().run_from_memory()

    def test_string_generator_and_dataset_roundtrip(self, tmp_path):
        from paddle1_tpu.distributed.fleet import \
            MultiSlotStringDataGenerator

        class G(MultiSlotStringDataGenerator):
            def generate_sample(self, line):
                def it():
                    yield [("f", ["0.5", "1.5"]), ("lbl", ["1"])]
                return it
        lines = G().run_from_memory()
        assert lines == ["2 0.5 1.5 1 1\n"]
        # the emitted protocol parses through the dataset reader
        p = tmp_path / "gen.txt"
        p.write_text("".join(lines))
        ds = paddle.io.QueueDataset()
        ds.set_filelist([str(p)])
        ds.set_rank_world(0, 1)
        rows = [r for r in iter(ds)]
        assert len(rows) == 1


class TestWorkerInfo:
    def test_main_process_none(self):
        assert paddle.io.get_worker_info() is None

    def test_worker_sees_info(self):
        seen = {}

        class DS(paddle.io.Dataset):
            def __len__(self):
                return 8

            def __getitem__(self, i):
                wi = paddle.io.get_worker_info()
                return np.asarray(
                    [i, -1 if wi is None else wi.id,
                     -1 if wi is None else wi.num_workers],
                    np.int64)
        dl = paddle.io.DataLoader(DS(), batch_size=4, num_workers=2,
                                  shuffle=False)
        batches = [np.asarray(b.numpy()) for b in dl]
        got = np.concatenate(batches)
        assert (got[:, 1] >= 0).all()      # worker id visible
        assert (got[:, 2] == 2).all()      # num_workers visible


class TestStaticJitShims:
    def test_append_backward_returns_param_grads(self):
        import paddle1_tpu.fluid as fluid
        import paddle1_tpu.static as S
        fluid.layers.reset_parameter_pass()
        x = to_tensor(np.ones((2, 3), np.float32))
        out = fluid.layers.fc(x, 4, name="ab_fc")
        pairs = S.append_backward(out.sum())
        assert pairs and all(g is not None for _, g in pairs)

    def test_program_state_roundtrip(self, tmp_path):
        import paddle1_tpu.fluid as fluid
        import paddle1_tpu.static as S
        fluid.layers.reset_parameter_pass()
        x = to_tensor(np.ones((1, 2), np.float32))
        fluid.layers.fc(x, 2, name="ps_fc")
        path = str(tmp_path / "m")
        S.save(None, path)
        st = S.load_program_state(path)
        assert st
        S.set_program_state(None, st)

    def test_traced_layer(self):
        from paddle1_tpu.jit import TracedLayer
        lin = paddle.nn.Linear(3, 2)
        x = to_tensor(np.ones((1, 3), np.float32))
        outs, traced = TracedLayer.trace(lin, [x])
        np.testing.assert_allclose(_np(traced(x)), _np(lin(x)),
                                   rtol=1e-6)
