"""Roofline share of the work under the `moe` scope (router, dispatch, the held
experts, combine, shared experts, balance term), in %
(chipbench.readings.roofline_share).  None where no expert layer ran."""

from chipbench.readings import roofline_share


def read(m):
    return roofline_share(m, "moe")
