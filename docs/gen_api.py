"""Generate the per-symbol API reference (docs/api/*.md) from the
package's own docstrings — the docs cannot drift from the code because
they ARE the code's docstrings (VERDICT round-4: per-symbol reference at
the reference's sphinx depth; autogen sanctioned).

Run from the repo root (CPU is fine)::

    JAX_PLATFORMS=cpu python docs/gen_api.py

Checked-in output: regenerate after changing public docstrings;
tests/L0/test_docs.py asserts the pages exist and cover the public
surface.
"""

from __future__ import annotations

import importlib
import inspect
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "api")

# page -> modules documented on it (order preserved)
PAGES = {
    "amp": ["apex_tpu.amp", "apex_tpu.amp.scaler", "apex_tpu.amp.autocast",
            "apex_tpu.fp16_utils"],
    "optimizers": ["apex_tpu.optimizers", "apex_tpu.multi_tensor_apply"],
    "normalization": ["apex_tpu.normalization"],
    "parallel": ["apex_tpu.parallel", "apex_tpu.comm"],
    "transformer": ["apex_tpu.transformer",
                    "apex_tpu.transformer.tensor_parallel",
                    "apex_tpu.transformer.pipeline_parallel",
                    "apex_tpu.transformer.functional",
                    "apex_tpu.transformer.context_parallel",
                    "apex_tpu.transformer.moe"],
    "kernels": ["apex_tpu.kernels", "apex_tpu.kernels.flash_attention",
                "apex_tpu.kernels.decode_attention",
                "apex_tpu.kernels.prefill_attention",
                "apex_tpu.kernels.layer_norm", "apex_tpu.kernels.xentropy",
                "apex_tpu.kernels.lm_head_loss",
                "apex_tpu.kernels.multi_tensor",
                "apex_tpu.kernels.group_norm",
                "apex_tpu.kernels.grouped_gemm",
                "apex_tpu.kernels.gated_delta", "apex_tpu.kernels.vmem"],
    "models": ["apex_tpu.models", "apex_tpu.models.bert",
               "apex_tpu.models.transformer_lm", "apex_tpu.models.zaya",
               "apex_tpu.models.qwen3_next", "apex_tpu.models.ling",
               "apex_tpu.models.lm_layers"],
    "layers": ["apex_tpu.mlp", "apex_tpu.fused_dense"],
    "utils": ["apex_tpu.utils", "apex_tpu.utils.checkpoint",
              "apex_tpu.utils.sharded_checkpoint", "apex_tpu.utils.pytree",
              "apex_tpu.utils.memory_report",
              "apex_tpu.utils.schedule_report", "apex_tpu.utils.compat",
              "apex_tpu.utils.chip", "apex_tpu.pyprof"],
    "telemetry": ["apex_tpu.telemetry", "apex_tpu.telemetry.sinks",
                  "apex_tpu.telemetry.summarize",
                  "apex_tpu.telemetry.tracing", "apex_tpu.log_util"],
    "serving": ["apex_tpu.serving", "apex_tpu.serving.kv_cache",
                "apex_tpu.serving.quant_common",
                "apex_tpu.serving.kv_quant",
                "apex_tpu.serving.weight_quant",
                "apex_tpu.serving.engine",
                "apex_tpu.serving.sharding",
                "apex_tpu.serving.prefix_cache",
                "apex_tpu.serving.host_tier",
                "apex_tpu.serving.speculative",
                "apex_tpu.serving.scheduler",
                "apex_tpu.serving.slo",
                "apex_tpu.serving.router",
                "apex_tpu.serving.routing_policy",
                "apex_tpu.serving.fleet",
                "apex_tpu.serving.fleet_worker",
                "apex_tpu.serving.faults",
                "apex_tpu.serving.lora"],
    "contrib": [
        "apex_tpu.contrib.bottleneck", "apex_tpu.contrib.clip_grad",
        "apex_tpu.contrib.conv_bias_relu", "apex_tpu.contrib.cudnn_gbn",
        "apex_tpu.contrib.fmha", "apex_tpu.contrib.focal_loss",
        "apex_tpu.contrib.gpu_direct_storage",
        "apex_tpu.contrib.group_norm", "apex_tpu.contrib.groupbn",
        "apex_tpu.contrib.index_mul_2d", "apex_tpu.contrib.layer_norm",
        "apex_tpu.contrib.multihead_attn",
        "apex_tpu.contrib.nccl_allocator", "apex_tpu.contrib.openfold_triton",
        "apex_tpu.contrib.optimizers", "apex_tpu.contrib.peer_memory",
        "apex_tpu.contrib.sparsity", "apex_tpu.contrib.transducer",
        "apex_tpu.contrib.xentropy",
    ],
}


def _public_names(mod):
    if hasattr(mod, "__all__"):
        return list(mod.__all__)
    return [n for n, v in vars(mod).items()
            if not n.startswith("_")
            and getattr(v, "__module__", None) == mod.__name__]


_ADDR_RE = None


def _scrub(text: str) -> str:
    """Default-value reprs carry memory addresses (`<object object at
    0x...>`, `<function zeros at 0x...>`) — nondeterministic across
    runs, which would make the checked-in pages permanently stale."""
    global _ADDR_RE
    if _ADDR_RE is None:
        import re

        _ADDR_RE = re.compile(r" at 0x[0-9a-f]+")
    return _ADDR_RE.sub("", text)


def _sig(obj) -> str:
    try:
        return _scrub(str(inspect.signature(obj)))
    except (TypeError, ValueError):
        return "(...)"


def _doc(obj) -> str:
    d = inspect.getdoc(obj)
    return d.strip() if d else "*(no docstring)*"


def _emit_symbol(f, name, obj, level="###"):
    if inspect.isclass(obj):
        f.write(f"{level} class `{name}`\n\n")
        f.write(_doc(obj) + "\n\n")
        # flax modules: dataclass fields are the constructor surface
        fields = getattr(obj, "__dataclass_fields__", None)
        if fields:
            shown = [n for n in fields
                     if n not in ("parent", "name")
                     and not n.startswith("_")]
            if shown:
                f.write("Fields: " + ", ".join(f"`{n}`" for n in shown)
                        + "\n\n")
        for mname, m in sorted(vars(obj).items()):
            if mname.startswith("_"):
                continue
            if fields and mname in fields:
                continue   # callable dataclass-field DEFAULTS, not methods
            # unwrap descriptors so properties and class/staticmethods
            # document like plain methods (classmethod objects are not
            # callable; property docs live on fget)
            tag = ""
            if isinstance(m, property):
                m, tag = m.fget, " [property]"
            elif isinstance(m, classmethod):
                m, tag = m.__func__, " [classmethod]"
            elif isinstance(m, staticmethod):
                m, tag = m.__func__, " [staticmethod]"
            if m is None or not callable(m):
                continue
            if inspect.getdoc(m):
                sig = "" if tag == " [property]" else _sig(m)
                f.write(f"- **`.{mname}{sig}`**{tag} — "
                        + _doc(m).splitlines()[0] + "\n")
        f.write("\n")
    elif callable(obj):
        f.write(f"{level} `{name}{_sig(obj)}`\n\n")
        f.write(_doc(obj) + "\n\n")
    else:
        f.write(f"{level} `{name}` = `{_scrub(repr(obj))}`\n\n")


def gen_page(page, modules, out=None):
    path = os.path.join(out or OUT, f"{page}.md")
    with open(path, "w") as f:
        f.write(f"# API reference — {page}\n\n")
        f.write("*Generated from docstrings by `docs/gen_api.py`; "
                "do not edit by hand.*\n\n")
        for modname in modules:
            mod = importlib.import_module(modname)
            f.write(f"## `{modname}`\n\n")
            moddoc = inspect.getdoc(mod)
            if moddoc:
                f.write(moddoc.strip() + "\n\n")
            explicit = hasattr(mod, "__all__")
            for name in _public_names(mod):
                if explicit and not hasattr(mod, name):
                    # __all__ is an explicit contract: a stale/typo'd
                    # entry must fail the build, not silently ship
                    # docs with the symbol missing
                    raise SystemExit(
                        f"{modname}.__all__ lists {name!r} but the "
                        "module has no such attribute")
                obj = getattr(mod, name, None)
                if obj is None or inspect.ismodule(obj):
                    continue
                _emit_symbol(f, name, obj)
    with open(path) as f:
        n = sum(1 for _ in f)
    print(f"  {path}: {n} lines")
    return n


def main(out=None):
    out = out or OUT
    os.makedirs(out, exist_ok=True)
    total = 0
    for page, modules in PAGES.items():
        total += gen_page(page, modules, out)
    idx = os.path.join(out, "index.md")
    with open(idx, "w") as f:
        f.write("# API reference\n\nGenerated per-symbol pages "
                "(`python docs/gen_api.py`):\n\n")
        for page in PAGES:
            f.write(f"- [{page}]({page}.md)\n")
    print(f"total: {total} lines")


if __name__ == "__main__":
    main()
