"""Places: device identity tags (reference: paddle/fluid/platform/place.h).

The TPU build's Place variant is {CPUPlace, TPUPlace}; ``CUDAPlace`` is kept
as an alias accepted for script compatibility (it selects the accelerator,
which here is the TPU chip). Device binding is resolved lazily through JAX's
backend — there is no dynload'd driver stack to manage (PJRT plays the role
of the reference's platform/dynload layer).
"""

import os

import jax


class Place:
    def __eq__(self, other):
        return type(self) is type(other) and self.__dict__ == other.__dict__

    def __hash__(self):
        return hash((type(self).__name__, tuple(sorted(self.__dict__.items()))))


class CPUPlace(Place):
    def __repr__(self):
        return "CPUPlace"

    def jax_device(self):
        return jax.devices("cpu")[0]


class TPUPlace(Place):
    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return "TPUPlace(%d)" % self.device_id

    def jax_device(self):
        devs = jax.devices()
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                "%r: this process has %d device(s) (%s)"
                % (self, len(devs), devs[0].platform))
        return devs[self.device_id]


class CUDAPinnedPlace(CPUPlace):
    def __repr__(self):
        return "CUDAPinnedPlace"


# Script-compatibility alias: "the accelerator" is the TPU in this build.
CUDAPlace = TPUPlace


def is_compiled_with_cuda():
    return False


def is_compiled_with_tpu():
    return True


def default_accelerator_place():
    devs = jax.devices()
    if devs and devs[0].platform != "cpu":
        return TPUPlace(0)
    return CPUPlace()


def cuda_device_count():
    """Accelerator count (name kept for API compat)."""
    return len([d for d in jax.devices() if d.platform != "cpu"]) or 1


def use_compilation_cache():
    """Point JAX's persistent compilation cache somewhere stable, for
    entry points that pay large compiles (chip_smoke.py,
    benchmarks/drivers/train.py) — never at import. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX has
    already taken the directory from it and no path is set in code;
    otherwise the cache is ``<checkout>/.jax_cache``, a fixed path (the
    path is part of the cache key, so one that moves never hits).
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
