// The machine's monotonic clock, in nanoseconds: the one clock of an
// actor's cycle (ISSUE 66). On Linux CLOCK_MONOTONIC is the kernel's,
// the same number in every process of the machine, and Python's
// time.monotonic_ns() reads it too, so a stamp the env server puts on a
// step message (env_server.h, runtime/env_server.py) can be subtracted
// from one the actor pool takes (actor_pool.h) with no synchronisation.
// Asked for by name: what steady_clock's epoch is, is the library's own
// business.

#pragma once

#include <time.h>

#include <cstdint>

namespace tbt {

inline int64_t monotonic_ns() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return static_cast<int64_t>(ts.tv_sec) * 1000000000 + ts.tv_nsec;
}

}  // namespace tbt
