"""The equal-programs check (docs/serving.md §Model families): the SHA-256 of the lowered text (StableHLO) of each serve
configuration's prefill and decode program, a Mosaic body's source locations stripped.  From the root of each of two trees:
python3 tools/lowered_serve.py <tag> [--tiny] [--dump DIR] [name ...] — the benchmark's serve configurations at full size (on the chip), or ``--tiny``: each
family's tiny one on the paged pool, GPT-2's on the int8 paged and the slot pool too (on the CPU); ``--dump`` keeps each text, ``DIR/<tag>.<name>.<program>.txt``."""
import base64, gc, hashlib, os, re, sys  # noqa: E401

sys.path.insert(0, os.getcwd())
import jax  # noqa: E402

BUILDERS = {"gpt2-xl-serve-paged": "build", "deepseek-v2-serve-ep4share": "build_deepseek_v2", "solar-open2-serve-ep8share": "build_solar_open2",
            "zaya1-8b-serve-ep2share": "build_zaya1", "keye-vl2-30b-serve-ep8share": "build_keye", "gigachat35-serve-ep16share": "build_gigachat35",
            "laguna-s21-serve-ep8stage": "build_laguna", "mimo-v2-flash-serve-ep16stage": "build_mimo"}
PAGED = {"kvcache": {"enabled": True, "page_len": 16}}
TINY = {"gpt2": ("gpt2", PAGED), "gpt2-int8": ("gpt2", {**PAGED, "kv_cache_dtype": "int8"}), "gpt2-slot": ("gpt2", {}),
        **{family: (family, PAGED) for family in ("deepseek_v2", "solar_open2", "zaya", "keye", "gigachat35", "laguna", "mimo_v2")}}


def normalised(text: str) -> str:
    """A lowered module's text with each Mosaic call's serialized body (MLIR bytecode, which carries the file paths and
    line numbers of the call stack the kernel was traced under) replaced by the hash of its assembly without locations."""
    from jax._src.lib import tpu as tpu_dialect
    from jax._src.lib.mlir import ir

    def body(m):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        tpu_dialect.register_dialect(ctx)
        with ctx:
            asm = ir.Module.parse(base64.b64decode(m.group(2)), ctx).operation.get_asm(enable_debug_info=False)
        return m.group(1) + "mosaic:" + hashlib.sha256(asm.encode()).hexdigest() + m.group(3)

    return re.sub(r'(body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)', body, text)


def engine(name: str, tiny: bool):
    if not tiny:
        from benchmark.manifest import Manifest

        return __import__("benchmark." + BUILDERS[name], fromlist=["serving_engine"]).serving_engine(Manifest().config(name), 51, jax.devices()[:1])
    import deepspeed_tpu.serving

    family, serving = TINY[name]
    mcfg = getattr(__import__("deepspeed_tpu.models." + family, fromlist=["x"]), family.upper() + "_TINY")
    inf = deepspeed_tpu.init_inference(model_config=mcfg, dtype=jax.numpy.float32, max_out_tokens=128, seed=3)
    return deepspeed_tpu.serving.ServingEngine(inf, config={"num_slots": 3, "max_len": 128, "prefill_chunk": 16, **serving})


if __name__ == "__main__":
    args = sys.argv[1:]
    tiny, dump = "--tiny" in args, args.pop(args.index("--dump") + 1) if "--dump" in args else None
    tag, *names = [a for a in args if not a.startswith("--")]
    for name in names or (TINY if tiny else BUILDERS):
        srv = engine(name, tiny)  # one at a time: a full-size engine fills the chip
        for which in ("prefill", "decode"):
            getattr(srv, "_get_" + which)()
            text = normalised(getattr(srv, f"_{which}_jit").lower(*getattr(srv, f"_{which}_abstract_args")()).as_text())
            print("LOWERED", tag, name, which, "sha256", hashlib.sha256(text.encode()).hexdigest(), "chars", len(text), flush=True)
            if dump:
                open(os.path.join(dump, f"{tag}.{name}.{which}.txt"), "w").write(text)
        del srv
        gc.collect()
