"""kv_update_ms: device time of the fused decode program
(``_decode_impl``) under the ``kv_update`` scope (the paged cache's page
gather, ``_bump_token`` shift and scatter, ``chipbench.scopes``) in the
traced window, over the scan steps it ran."""
from pathlib import Path

from chipbench import scopes

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    tab = scopes.table(ROOT)
    if tab is None:
        return None
    return scopes.per_decode_step(ctx, scopes.under(
        tab["programs"].get("_decode_impl", {}), "kv_update"))
