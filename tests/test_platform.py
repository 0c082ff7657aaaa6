"""Start-up and device selection: where the compile cache goes
(utils/platform.configure_compilation_cache) and device.get_device
honouring ``device_args.device_type``."""

import os
import re
import types

import jax
import pytest

from fedml_tpu import device
from fedml_tpu.utils import platform

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


class TestCompilationCache:
    def test_env_var_set_means_the_program_writes_nothing(
            self, monkeypatch, config_updates):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert platform.configure_compilation_cache() == "/somewhere/else"
        assert config_updates == []  # jax reads the variable itself

    def test_unset_means_the_fixed_in_checkout_path(
            self, monkeypatch, config_updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
        assert platform.configure_compilation_cache() == want
        assert platform.configure_compilation_cache() == want  # stable
        assert config_updates == [("jax_compilation_cache_dir", want)] * 2

    def test_the_cache_dir_has_exactly_one_writer(self):
        """``git grep jax_compilation_cache_dir`` over the program (tests
        aside): one file names it, and sets it once."""
        hits = []
        for dirpath, dirs, files in os.walk(ROOT):
            dirs[:] = [d for d in dirs if not d.startswith((".", "_"))
                       and d not in ("tests", "chiprun_out")]
            hits += [os.path.relpath(os.path.join(dirpath, f), ROOT)
                     for f in files if f.endswith(".py")
                     and "jax_compilation_cache_dir"
                     in open(os.path.join(dirpath, f)).read()]
        writer = os.path.join("fedml_tpu", "utils", "platform.py")
        assert hits == [writer]
        assert len(re.findall(r'update\(\s*"jax_compilation_cache_dir"',
                              open(os.path.join(ROOT, writer)).read())) == 1

    def test_init_places_the_cache(self, monkeypatch, config_updates):
        import fedml_tpu
        from fedml_tpu.arguments import Arguments

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        fedml_tpu.init(Arguments.from_dict(
            {"common_args": {"random_seed": 0}}), should_init_logs=False)
        assert ("jax_compilation_cache_dir",
                os.path.join(ROOT, ".jax_cache")) in config_updates


class TestDeviceType:
    def test_a_config_that_says_tpu_raises_on_a_host_without_one(self):
        with pytest.raises(RuntimeError, match="device_type is 'tpu'"):
            device.get_device(types.SimpleNamespace(device_type="tpu"))

    def test_the_backend_that_answered_passes(self):
        want = jax.devices()[0]
        assert device.get_device(types.SimpleNamespace(device_type="cpu")) == want
        assert device.get_device(types.SimpleNamespace(device_type="CPU")) == want

    def test_no_device_type_means_whatever_jax_selected(self):
        assert device.get_device() == jax.devices()[0]
        assert device.get_device(types.SimpleNamespace()) == jax.devices()[0]


def test_force_cpu_backend_is_the_public_config_switch(config_updates):
    platform.force_cpu_backend()
    assert config_updates == [("jax_platforms", "cpu")]
