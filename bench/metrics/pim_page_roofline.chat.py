"""RowClone page copy and init: the HBM bytes the ops must move (pages
by kind x page bytes over the layers that hold KV and both arenas; read
and write for a copy, write for an init, as the architecture module
counts them) over the device time of their programs, as a share of the
HBM roofline, in percent."""
from bench.core.readers import counter_delta

MODULES = ("pim_page_init_batched", "pim_page_copy_batched")


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    secs = sum(v for k, v in t.module_s.items()
               if any(m in k for m in MODULES))
    inits = counter_delta(ctx, "pages_zeroed", traced=True)
    copies = counter_delta(ctx, "cow_copies", traced=True)
    if not secs or not (inits or copies):
        return None
    need = ctx.arch.page_op_bytes(ctx.cfg, ctx.dep["page_size"], inits,
                                  copies)
    return 100.0 * need / ctx.peak["hbm_bytes_per_s"] / secs
