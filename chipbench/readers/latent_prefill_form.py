"""Share, in percent, of the window's cached prefill programs over a
latent (MLA) cache that took the absorbed form (attention over the
gathered latents themselves, the up-projection folded into the queries
and the outputs) and not the up-projected one: ``latent_prefill_absorbed``
over ``latent_prefill_absorbed + latent_prefill_up_projected`` of the
window's step records, the program's count of its dispatches by the form
``models/decoder.py::latent_prefill_form`` picks from their shapes
(``tpu:latent_prefill_form_total{form}`` is the same count since
start-up). 0 where every one up-projected. Nothing where no record
carries either count: another model, or a program without the rule."""


def read(ctx, params):
    absorbed = sum(s.get("latent_prefill_absorbed", 0) for s in ctx.steps)
    other = sum(s.get("latent_prefill_up_projected", 0) for s in ctx.steps)
    if not absorbed + other:
        return None
    return 100.0 * absorbed / (absorbed + other)
