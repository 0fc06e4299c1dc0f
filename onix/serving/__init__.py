"""Serving layer (r12): the device-resident model bank.

ONI's product shape is one (θ, φ) model per datatype × day — and the
north star multiplies that by tenant. The batch pipelines in
`onix/pipelines` assume exactly one model at a time; this package is
the piece that turns the scorer into a SERVICE: many tenants' tables
stacked into bank-shaped device arrays, mixed-tenant request batches
scored through ONE jitted program, LRU residency for banks larger than
device memory, and a load harness that replays skewed tenant traffic.
"""

from onix.serving.model_bank import (BankRefusal, BankService, ModelBank,
                                     ScoreRequest, TenantModel)

__all__ = ["BankRefusal", "BankService", "ModelBank", "ScoreRequest",
           "TenantModel"]
