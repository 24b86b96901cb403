"""What a family whose recurrent state lies in the pool's STATE CLASS
(``serve/kv_pages.py``: a block a live sequence, addressed by sequence and
not by page) refuses on the serve path, stated ONCE for every such family
(``models/solar_open2.py``: KDA's matrix a head; ``models/jamba.py``:
Mamba's ``[d_state, channels]`` block; ``models/brumby.py``: power
retention's ``S`` and ``Z`` a kv head). It lives beside the models because
``serve`` imports them; ``ServeEngine`` reads it as the family's
``SERVE_REFUSES`` (``serve/engine.refuse_for_family``)."""

# option -> why, each with the module that would have to change
STATE_CLASS_REFUSES = {
    "prefix_cache": "a hit needs the recurrent state AT the hit's last "
                    "token, and the state class keeps a sequence's newest "
                    "alone (snapshots at page boundaries: "
                    "scheduler.PrefixCache)",
    "speculate": "a rejected draft would have to roll the recurrent state "
                 "back (serve/spec.py verifies into the live state)",
    "decode_horizon": "a lane that ends inside a horizon would go on "
                      "updating its state block (engine.horizon_for masks "
                      "page tables alone)",
    "host_tier_bytes": "serve/tiering.py gathers and scatters page ids "
                       "alone, and the state has none",
    "disaggregation": "serve/transport.py hands over page ids alone, and "
                      "the state has none",
    "engine swap": "Scheduler.adopt seats page ids alone",
    "plan / shard_kv": "the tp serve mesh splits kv heads; the state class "
                       "has no sharding rule (serve/sharding.py)",
    "kv_dtype='int8'": "the state class is stored in float",
    "weight_dtype='int8'": "serve/weights.py selects llama leaves only",
    "max_adapters": "the LoRA hooks wrap llama's projections",
}
