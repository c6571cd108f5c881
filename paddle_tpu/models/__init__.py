"""Flagship model families built on the TP layer stack.

Reference analog: PaddleNLP-style model zoo driven by the framework's fleet
TP/PP layers (the reference repo itself ships the layer stack —
fleet/layers/mpu — and fused transformer ops; the model graph lives here so
benchmarks and the driver entry have a first-class citizen to run).
"""
from . import llama
from .llama import (LlamaConfig, LlamaForCausalLM, LlamaModel,
                    llama_config)

__all__ = ["llama", "LlamaConfig", "LlamaForCausalLM", "LlamaModel",
           "llama_config"]


# lazy model families: submodule name → its public names
_LAZY = {
    "gpt": ("GPTConfig", "GPTModel", "GPTForCausalLM", "gpt_config"),
    "ernie": ("ErnieMoEConfig", "ErnieMoEModel", "ErnieMoEForMaskedLM",
              "ernie_moe_config"),
    "gigachat35": ("GigaChat35Config", "GigaChat35Model",
                   "GigaChat35ForCausalLM"),
}


def __getattr__(name):
    for sub, names in _LAZY.items():
        if name == sub or name in names:
            import importlib

            mod = importlib.import_module(f".{sub}", __name__)
            globals()[sub] = mod
            for n in names:
                globals()[n] = getattr(mod, n)
            return globals()[name]
    raise AttributeError(name)


def __dir__():
    out = set(globals()) | set(_LAZY)
    for names in _LAZY.values():
        out |= set(names)
    return sorted(out)
