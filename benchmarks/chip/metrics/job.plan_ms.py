"""The harness's ``job.plan`` span (``plan_shares_skew`` on the job's rows; its children
``plan.detect`` and ``plan.solve``) per window job, in ms."""
from chipbench import spans


def read(run):
    return spans.ms_per_batch(run, "job.plan")
