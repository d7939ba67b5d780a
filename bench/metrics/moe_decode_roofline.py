"""Share of its roofline that the routed-expert decode kernel reaches, in
%: the least time for the weights a decode step must read, the
``num_experts_per_tok`` experts' gate, up and down matrices in every layer
for each traced run of the engine's step (``_decode_all`` module events;
every engine step has a live lane, which routes to that many experts), at
the chip's HBM bandwidth, over the device time of the ops named
``moe_decode``. A floor: lanes that route to more distinct experts make
the kernel read more, so the share never passes 100 %."""
KERNEL = "moe_decode"


def expert_bytes(model, itemsize: int) -> int:
    """One live lane's experts in every layer: ``num_experts_per_tok``
    times the gate, up and down matrices of ``hidden_size`` by
    ``intermediate_size``."""
    return (model["num_hidden_layers"] * model["num_experts_per_tok"] * 3
            * model["hidden_size"] * model["intermediate_size"] * itemsize)


def op_name(event_name: str) -> str:
    """The operation's own name: a trace names an op by its HLO text
    (``%name = shape op(operands)``), whose operands may name others."""
    return event_name.split(" ", 1)[0].lstrip("%")


def read(run):
    if (run.peak is None or not run.model.get("num_local_experts")
            or not run.trace.ops or not run.trace.modules):
        return None
    steps = sum("_decode_all" in e.name for e in run.trace.modules[0])
    busy = sum(e.end - e.start for e in run.trace.ops[0]
               if op_name(e.name).startswith(KERNEL))
    if not steps or not busy:
        return None
    import jax.numpy as jnp

    itemsize = jnp.dtype(run.cell.deployment["dtype"]).itemsize
    least = (steps * expert_bytes(run.model, itemsize)
             / run.peak["hbm_bytes_per_s"])
    return 100.0 * least / (busy / 1e9)
