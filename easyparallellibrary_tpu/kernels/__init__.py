from easyparallellibrary_tpu.kernels.flash_attention import (
    flash_attention, flash_attention_qkv)
from easyparallellibrary_tpu.kernels.dsa_index import (
    dsa_index_pallas, dsa_index_reference)
from easyparallellibrary_tpu.kernels.gdn_scan import (
    gdn_scan_pallas, gdn_scan_reference)
from easyparallellibrary_tpu.kernels.kv_write import (
    kv_write_pallas, kv_write_reference)
from easyparallellibrary_tpu.kernels.moe_gmm import (
    moe_gmm_pallas, moe_gmm_reference)
from easyparallellibrary_tpu.kernels.slot_attention import (
    slot_attention_pallas, slot_attention_reference)
from easyparallellibrary_tpu.kernels.ssm_scan import (
    ssm_scan_pallas, ssm_scan_reference)
from easyparallellibrary_tpu.kernels.paged_attention import (
    paged_attention, paged_attention_pallas, paged_attention_reference,
    set_paged_attention_impl,
)

__all__ = [
    "dsa_index_pallas", "dsa_index_reference",
    "flash_attention", "flash_attention_qkv",
    "gdn_scan_pallas", "gdn_scan_reference",
    "kv_write_pallas", "kv_write_reference",
    "moe_gmm_pallas", "moe_gmm_reference",
    "paged_attention", "paged_attention_pallas",
    "paged_attention_reference", "set_paged_attention_impl",
    "slot_attention_pallas", "slot_attention_reference",
    "ssm_scan_pallas", "ssm_scan_reference",
]
