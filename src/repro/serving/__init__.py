from repro.serving.scheduler import PackageScheduler, Request

__all__ = ["PackageScheduler", "Request"]
