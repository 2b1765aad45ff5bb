// Native batch read of /proc/<pid>/stat starttimes for the per-window
// pid-identity check (process/identity.py).
//
// The check reads every listed pid of every window; its cost is the
// system calls of a read and the interpreter between them. Python's
// buffered open() is about seven system calls a file (openat, fstat,
// ioctl, lseek, two reads, close), each of which drops and retakes the
// GIL. Here a read is open, read, close, the parse is in C, and the
// whole batch runs inside one ctypes call, so the GIL is released from
// the first pid to the last. What is read and how it is parsed is
// identity.read_starttime's rule, which stays the reference of the
// tests: at most `cap` bytes, the last ')' (comm may hold spaces and
// parens), the 20th whitespace-separated field after it (field 22 of
// the line).

#include <cerrno>
#include <climits>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>

#include <fcntl.h>
#include <unistd.h>

namespace {

// Per-pid codes in out[i]; a starttime is never negative.
constexpr int64_t kAbsent = -1;     // no such file, or it cannot be read
constexpr int64_t kOversized = -2;  // more than `cap` bytes: not procfs
constexpr int64_t kGarbled = -3;    // no ')', too few fields, no number

// bytes.split()'s separators.
inline bool is_space(unsigned char c) {
  return c == ' ' || (c >= '\t' && c <= '\r');
}

// Field 22 of a stat line held in buf[0, len), or kGarbled.
int64_t parse_starttime(const char* buf, int64_t len) {
  int64_t p = len;
  while (p > 0 && buf[p - 1] != ')') p--;
  if (p == 0) return kGarbled;
  int64_t start = 0, end = 0;
  for (int field = 0; field < 20; field++) {
    while (p < len && is_space(buf[p])) p++;
    start = p;
    while (p < len && !is_space(buf[p])) p++;
    end = p;
    if (start == end) return kGarbled;  // fewer than 20 fields
  }
  if (buf[start] == '+') start++;
  if (start == end) return kGarbled;
  int64_t v = 0;
  for (int64_t i = start; i < end; i++) {
    int d = buf[i] - '0';
    if (d < 0 || d > 9 || v > (INT64_MAX - d) / 10) return kGarbled;
    v = v * 10 + d;
  }
  return v;
}

// The file's bytes into buf (room for cap + 1): the count read, which is
// cap + 1 for a file larger than the cap, or -1 when it cannot be read.
int64_t read_capped(const char* path, char* buf, int64_t cap) {
  int fd;
  do {
    fd = open(path, O_RDONLY | O_CLOEXEC);
  } while (fd < 0 && errno == EINTR);
  if (fd < 0) return -1;
  int64_t len = 0;
  while (len <= cap) {
    ssize_t want = static_cast<ssize_t>(cap + 1 - len);
    ssize_t r = read(fd, buf + len, want);
    if (r < 0 && errno == EINTR) continue;
    if (r < 0) len = -1;
    if (r <= 0) break;
    len += r;
    // A stat record is one line. A read that came back short and ends
    // the line has reached the end of a regular file and of a procfs
    // record alike, so no further read is made to be told 0; any other
    // short read is taken and the loop reads on.
    if (r < want && buf[len - 1] == '\n') break;
  }
  close(fd);
  return len;
}

}  // namespace

extern "C" {

// out[i] = starttime of pids[i] read from "<root>/<pid>/stat", or one of
// the negative codes above. Returns how many were read and parsed, or
// -1 when the call itself could not run (bad arguments, no memory): the
// caller then reads the window its own way. `root` is "/proc" in the
// agent; tests point it at a tree of files.
int64_t pa_read_starttimes(const char* root, const int64_t* pids, int64_t n,
                           int64_t cap, int64_t* out) {
  if (!root || !pids || !out || n < 0 || cap < 1 || cap > (1 << 30))
    return -1;
  char path[PATH_MAX];
  if (strlen(root) + 32 > sizeof(path)) return -1;
  char* buf = static_cast<char*>(malloc(static_cast<size_t>(cap) + 1));
  if (!buf) return -1;
  int64_t parsed = 0;
  for (int64_t i = 0; i < n; i++) {
    snprintf(path, sizeof(path), "%s/%lld/stat", root,
             static_cast<long long>(pids[i]));
    int64_t len = read_capped(path, buf, cap);
    out[i] = len < 0 ? kAbsent
             : len > cap ? kOversized
                         : parse_starttime(buf, len);
    parsed += out[i] >= 0;
  }
  free(buf);
  return parsed;
}

}  // extern "C"
