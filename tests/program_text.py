"""The text of a DecodeEngine program's executable, for tests that pin
programs a change must leave alone (test_paged_programs_pinned.py).

A bundle of ``build_paged_programs`` is lowered as the engine dispatches
it (core/executor.py ``Executor._jit``: the pools a fifth, donated
argument) from the shapes its program declares, no weight made."""
import base64
import hashlib
import re

import jax
import jax.numpy as jnp
from jax._src.interpreters import mlir
from jax._src.lib import tpu
from jax._src.lib.mlir import ir

from paddle_tpu.core.executor import make_stepped
from paddle_tpu.core.lowering import lower_program


def lower_bundle(bundle, n_pools, sharding=None):
    """``jax.stages.Lowered`` of one program bundle; ``sharding``: where
    every argument lies (a described chip's: the lowering is then for
    that chip's compiler)."""
    gb = bundle["program"].global_block()

    def abstract(name):
        v = gb.vars[name]
        return jax.ShapeDtypeStruct(tuple(v.shape), jnp.dtype(v.dtype),
                                    sharding=sharding)

    fetch = [v if isinstance(v, str) else v.name for v in bundle["fetch"]]
    stepped = make_stepped(lower_program(bundle["program"], fetch, "test"))
    pools = list(bundle["feeds"][-n_pools:])

    def fn(rw, ro, feed, step_seed, given):
        return stepped(rw, ro, dict(feed, **dict(zip(pools, given))),
                       step_seed)

    ro = {n: abstract(n) for n, v in sorted(gb.vars.items())
          if v.persistable}
    feeds = {n: abstract(n) for n in bundle["feeds"][:-n_pools]}
    return jax.jit(fn, donate_argnums=(4,)).lower(
        {}, ro, feeds,
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sharding),
        [abstract(n) for n in pools])


def without_kernel_locations(text):
    """StableHLO ``text`` with the serialized body of every Mosaic kernel
    (a Pallas call lowered for the chip) replaced by the kernel printed
    WITHOUT its debug locations. A body names the files, the functions
    and the LINES it was traced through, the checkout's path among them:
    two checkouts of one tree differ there, and so does a program whose
    kernel a change left alone but moved down its file. What is left is
    what the chip is asked to run."""
    def printed(match):
        context = mlir.make_ir_context()
        tpu.register_dialect(context)
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            return module.operation.get_asm(enable_debug_info=False)

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', printed, text)


def chip_fingerprint(lowered):
    """sha256 of the StableHLO text of a program lowered for a described
    chip, its kernels free of their debug locations."""
    return hashlib.sha256(without_kernel_locations(
        lowered.as_text()).encode()).hexdigest()[:16]


def compiled_fingerprint(lowered):
    """sha256 of what the chip's compiler LEAVES of a program lowered for a
    described chip: the optimized module, every instruction with its name,
    shape, layout and operands, in its schedule, without what names the
    source it was traced from (an instruction's ``metadata``: scopes,
    frames; the tables of files, functions and lines before the module; a
    kernel's serialized body, which ``chip_fingerprint`` holds free of its
    locations). Two programs whose StableHLO differs in the order of
    independent operations and nothing else compile to the same module."""
    text = lowered.compile().as_text()
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r'"body":"[A-Za-z0-9+/=]+"', "", text)
    kept = [line for line in text.splitlines()
            if not re.match(r'\d+ ["{]', line)]
    return hashlib.sha256("\n".join(kept).encode()).hexdigest()[:16]


def fingerprint(lowered):
    """(sha256 of the StableHLO text the program lowers to, instructions
    of the optimized module): the first is what the program asks for,
    whatever the backend makes of it; the second what this backend's
    compiler left."""
    text = lowered.as_text()
    optimized = lowered.compile().as_text()
    count = len(re.findall(r"^\s+(?:ROOT )?%?[\w.\-]+ = ", optimized,
                           re.M))
    return hashlib.sha256(text.encode()).hexdigest()[:16], count


def probe_form(bundle):
    """A decode bundle with its ``probe`` fetch set in place of the one
    the loop dispatches (models/latent_moe.py ``build_block_programs``):
    the same Program, every step's logits and picks among its results."""
    return {**bundle, **bundle["probe"]}


def bundles_of(programs):
    """label -> bundle of every target-model program of a
    PagedDecodePrograms."""
    out = {f"prefill_{b}": v for b, v in sorted(programs.prefill.items())}
    out["decode"] = programs.decode
    if programs.chunk is not None:
        out["chunk"] = programs.chunk
    return out
