"""Op-version / artifact compat registry (reference
op_version_registry.h): jit.save artifacts carry versions,
loaders refuse newer-runtime artifacts and warn across semantic
changes."""

import json
import warnings

import numpy as np
import pytest

import paddle1_tpu as paddle
from paddle1_tpu.framework import op_version as opv
from paddle1_tpu.jit import InputSpec


def _saved_model(tmp_path):
    model = paddle.nn.Linear(4, 2)
    path = str(tmp_path / "m/linear")
    paddle.jit.save(model, path,
                    input_spec=[InputSpec([1, 4], "float32", "x")])
    return model, path


class TestRegistry:
    def test_versions_monotonic(self):
        assert opv.op_version("flash_attention") >= 2
        assert opv.op_version("never_registered_op") == 1
        with pytest.raises(ValueError, match="backwards"):
            opv.register_op_version("flash_attention", 1)

    def test_snapshot_shape(self):
        snap = opv.snapshot()
        assert snap["format_version"] == opv.FORMAT_VERSION
        assert "flash_attention" in snap["op_versions"]
        assert snap["framework_version"]


class TestArtifactCompat:
    def test_roundtrip_embeds_and_passes(self, tmp_path):
        model, path = _saved_model(tmp_path)
        cfg = json.load(open(path + ".pdconfig"))
        assert cfg["compat"]["format_version"] == opv.FORMAT_VERSION
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # clean load: no warnings
            loaded = paddle.jit.load(path)
        x = np.ones((1, 4), np.float32)
        np.testing.assert_allclose(
            np.asarray(loaded(paddle.to_tensor(x)).numpy()),
            np.asarray(model(paddle.to_tensor(x)).numpy()), rtol=1e-6)

    def test_newer_format_refuses(self, tmp_path):
        _, path = _saved_model(tmp_path)
        cfg = json.load(open(path + ".pdconfig"))
        cfg["compat"]["format_version"] = opv.FORMAT_VERSION + 1
        json.dump(cfg, open(path + ".pdconfig", "w"))
        with pytest.raises(opv.OpVersionError, match="upgrade"):
            paddle.jit.load(path)

    def test_newer_op_version_refuses(self, tmp_path):
        _, path = _saved_model(tmp_path)
        cfg = json.load(open(path + ".pdconfig"))
        cfg["compat"]["op_versions"]["flash_attention"] = 99
        json.dump(cfg, open(path + ".pdconfig", "w"))
        with pytest.raises(opv.OpVersionError, match="flash_attention"):
            paddle.jit.load(path)

    def test_older_op_version_warns_with_notes(self, tmp_path):
        _, path = _saved_model(tmp_path)
        cfg = json.load(open(path + ".pdconfig"))
        cfg["compat"]["op_versions"]["flash_attention"] = 1
        json.dump(cfg, open(path + ".pdconfig", "w"))
        with pytest.warns(UserWarning, match="LSE layout"):
            paddle.jit.load(path)

    def test_preversioning_artifact_warns(self, tmp_path):
        _, path = _saved_model(tmp_path)
        cfg = json.load(open(path + ".pdconfig"))
        del cfg["compat"]
        json.dump(cfg, open(path + ".pdconfig", "w"))
        with pytest.warns(UserWarning, match="pre-versioning"):
            paddle.jit.load(path)
