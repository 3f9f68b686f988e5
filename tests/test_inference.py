"""Inference deployment surface: Config/Predictor over
the jit.save artifact, plus the C ABI (embedded-interpreter capi.cc) —
reference paddle_api.h:85-301 and inference/capi/."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle1_tpu as paddle
from paddle1_tpu.core.tensor import to_tensor


@pytest.fixture(scope="module")
def lenet_artifact(tmp_path_factory):
    from paddle1_tpu.jit import InputSpec, save
    from paddle1_tpu.vision.models.lenet import LeNet
    d = tmp_path_factory.mktemp("export")
    base = str(d / "lenet")
    model = LeNet()
    model.eval()
    save(model, base,
         input_spec=[InputSpec([4, 1, 28, 28], "float32", name="image")])
    x = np.random.default_rng(0).standard_normal(
        (4, 1, 28, 28)).astype(np.float32)
    ref = np.asarray(model(to_tensor(x)).numpy())
    return base, x, ref


class TestConfigPredictor:
    def test_config_surface(self, lenet_artifact):
        base, _, _ = lenet_artifact
        from paddle1_tpu.inference import Config
        cfg = Config(base + ".pdmodel")
        assert cfg.model_program_path().endswith(".pdmodel")
        assert cfg.params_file_path().endswith(".pdiparams")
        cfg.disable_gpu()
        assert not cfg.use_gpu()
        cfg.enable_use_gpu(100, 0)
        assert cfg.use_gpu() and cfg.gpu_device_id() == 0
        cfg.switch_ir_optim(True)
        cfg.enable_memory_optim()
        cfg.set_cpu_math_library_num_threads(4)
        assert cfg.cpu_math_library_num_threads() == 4
        s = cfg.summary()
        assert "model file" in s and "device" in s

    def test_config_model_dir_form(self, lenet_artifact):
        base, _, _ = lenet_artifact
        from paddle1_tpu.inference import Config
        cfg = Config(os.path.dirname(base))
        assert cfg.model_program_path() == base + ".pdmodel"

    def test_predictor_run_positional(self, lenet_artifact):
        base, x, ref = lenet_artifact
        from paddle1_tpu.inference import Config, create_predictor
        pred = create_predictor(Config(base + ".pdmodel"))
        assert pred.get_input_names() == ["image"]
        outs = pred.run([x])
        np.testing.assert_allclose(outs[0], ref, rtol=1e-5, atol=1e-5)

    def test_predictor_zero_copy_handles(self, lenet_artifact):
        base, x, ref = lenet_artifact
        from paddle1_tpu.inference import Config, create_predictor
        pred = create_predictor(Config(base + ".pdmodel"))
        h = pred.get_input_handle("image")
        h.reshape([4, 1, 28, 28])
        h.copy_from_cpu(x)
        pred.run()
        out = pred.get_output_handle(pred.get_output_names()[0])
        np.testing.assert_allclose(out.copy_to_cpu(), ref, rtol=1e-5,
                                   atol=1e-5)
        assert out.shape() == [4, 10]

    def test_no_sidecar_fallback_input_count(self, lenet_artifact,
                                             tmp_path):
        """Review finding: without the .pdconfig sidecar (pre-sidecar
        artifacts), the input count must come from in_tree minus param
        leaves — not one phantom input per parameter."""
        import shutil
        base, x, ref = lenet_artifact
        for ext in (".pdmodel", ".pdiparams"):
            shutil.copy(base + ext, str(tmp_path / ("old" + ext)))
        from paddle1_tpu.inference import Config, create_predictor
        pred = create_predictor(Config(str(tmp_path / "old.pdmodel")))
        assert pred.get_input_names() == ["input_0"]
        outs = pred.run([x])
        np.testing.assert_allclose(outs[0], ref, rtol=1e-5, atol=1e-5)

    def test_missing_model_raises(self):
        from paddle1_tpu.inference import Config, Predictor
        with pytest.raises(FileNotFoundError):
            Predictor(Config("/tmp/definitely_missing_model.pdmodel"))

    def test_unknown_input_name(self, lenet_artifact):
        base, _, _ = lenet_artifact
        from paddle1_tpu.inference import Config, create_predictor
        pred = create_predictor(Config(base + ".pdmodel"))
        with pytest.raises(KeyError):
            pred.get_input_handle("nope")


C_DRIVER = textwrap.dedent(r"""
    #include <stdio.h>
    #include <stdint.h>
    #include <stdlib.h>
    #include <dlfcn.h>

    typedef void* (*create_fn)(const char*, const char*);
    typedef int (*run_fn)(void*, const float**, const int64_t*,
                          const int*, int, int, float*, int64_t,
                          int64_t*, int*);
    typedef void (*destroy_fn)(void*);
    typedef const char* (*err_fn)(void);

    int main(int argc, char** argv) {
      /* argv: 1=libpaddle1_capi.so 2=model_base 3=input.bin 4=output.bin */
      void* so = dlopen(argv[1], RTLD_NOW | RTLD_GLOBAL);
      if (!so) { fprintf(stderr, "dlopen: %s\n", dlerror()); return 2; }
      create_fn create = (create_fn)dlsym(so, "p1_predictor_create");
      run_fn run = (run_fn)dlsym(so, "p1_predictor_run_f32");
      destroy_fn destroy = (destroy_fn)dlsym(so, "p1_predictor_destroy");
      err_fn lasterr = (err_fn)dlsym(so, "p1_last_error");
      if (!create || !run || !destroy) { fprintf(stderr, "dlsym\n"); return 2; }

      void* h = create(argv[2], "cpu");
      if (!h) { fprintf(stderr, "create: %s\n", lasterr()); return 3; }

      float* in = (float*)malloc(4 * 1 * 28 * 28 * sizeof(float));
      FILE* f = fopen(argv[3], "rb");
      fread(in, sizeof(float), 4 * 28 * 28, f);
      fclose(f);

      int64_t shape[4] = {4, 1, 28, 28};
      int ndims = 4;
      const float* ins[1] = {in};
      float out[40];
      int64_t out_shape[8];
      int out_rank = 8;
      int rc = run(h, ins, shape, &ndims, 1, 0, out, 40, out_shape,
                   &out_rank);
      if (rc != 0) { fprintf(stderr, "run: %s\n", lasterr()); return 4; }
      if (out_rank != 2 || out_shape[0] != 4 || out_shape[1] != 10) {
        fprintf(stderr, "bad shape %d\n", out_rank); return 5;
      }
      FILE* g = fopen(argv[4], "wb");
      fwrite(out, sizeof(float), 40, g);
      fclose(g);
      destroy(h);
      printf("C-OK\n");
      return 0;
    }
""")


class TestCAPI:
    def test_c_level_smoke(self, lenet_artifact, tmp_path):
        """Build libpaddle1_capi.so, compile a pure-C driver, load the
        exported LeNet from C, run, and compare with the Python result."""
        base, x, ref = lenet_artifact
        from paddle1_tpu.core.native import build_capi
        so = build_capi()
        if so is None:
            pytest.skip("toolchain cannot build the capi .so")

        csrc = tmp_path / "driver.c"
        csrc.write_text(C_DRIVER)
        exe = tmp_path / "driver"
        comp = subprocess.run(["gcc", str(csrc), "-o", str(exe), "-ldl"],
                              capture_output=True)
        assert comp.returncode == 0, comp.stderr.decode()

        inp = tmp_path / "input.bin"
        outp = tmp_path / "output.bin"
        x.astype(np.float32).tofile(inp)

        env = dict(os.environ)
        # the embedded interpreter must find the repo and run on CPU
        # (same recipe as __graft_entry__.py)
        env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        env = {k: v for k, v in env.items()
               if not k.startswith("TPU_")}
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run([str(exe), so, base, str(inp), str(outp)],
                           capture_output=True, timeout=300, env=env)
        assert r.returncode == 0, (r.stdout.decode(), r.stderr.decode())
        assert b"C-OK" in r.stdout
        got = np.fromfile(outp, np.float32).reshape(4, 10)
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


class TestQuantizedInference:
    """Weight-only int8/bf16 predictor mode (reference
    mkldnn_quantizer.cc role, TPU-native form)."""

    def _artifact(self, tmp_path):
        import paddle1_tpu as paddle
        from paddle1_tpu.jit import InputSpec
        paddle.seed(0)
        model = paddle.nn.Sequential(
            paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
            paddle.nn.Linear(16, 4))
        path = str(tmp_path / "q/model")
        paddle.jit.save(model, path,
                        input_spec=[InputSpec([2, 8], "float32", "x")])
        return model, path

    def test_int8_weight_only_close_to_fp32(self, tmp_path):
        from paddle1_tpu import inference
        model, path = self._artifact(tmp_path)
        x = np.random.default_rng(0).standard_normal((2, 8)).astype(
            np.float32)

        cfg = inference.Config(path + ".pdmodel")
        ref = inference.create_predictor(cfg).run([x])[0]

        qcfg = inference.Config(path + ".pdmodel")
        qcfg.enable_quantized_inference()  # int8 default
        assert qcfg.precision_mode() == inference.PrecisionType.Int8
        out = inference.create_predictor(qcfg).run([x])[0]
        assert out.shape == ref.shape
        # int8 weight-only: small quantization error, same prediction
        err = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9)
        assert err < 0.05, err
        assert not np.allclose(out, ref)  # actually quantized

    def test_bf16_mode_runs(self, tmp_path):
        from paddle1_tpu import inference
        _, path = self._artifact(tmp_path)
        cfg = inference.Config(path + ".pdmodel")
        cfg.enable_quantized_inference(
            inference.PrecisionType.Bfloat16)
        out = inference.create_predictor(cfg).run(
            [np.ones((2, 8), np.float32)])[0]
        assert out.shape == (2, 4)

    def test_bad_precision_rejected(self):
        from paddle1_tpu import inference
        cfg = inference.Config()
        with pytest.raises(ValueError, match="Int8"):
            cfg.enable_quantized_inference(
                inference.PrecisionType.Half)
